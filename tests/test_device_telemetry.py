"""Device telemetry plane (obs/device): HBM occupancy inventory, sampled
kernel timing, compile accounting, and the /debug/device + horaectl
surfaces (ISSUE 15)."""

import asyncio

import numpy as np
import pytest

import horaedb_tpu
from horaedb_tpu.obs.device import (
    compile_stats,
    device_inventory,
    occupancy_totals,
)
from horaedb_tpu.utils import querystats
from horaedb_tpu.utils.events import EVENT_STORE
from horaedb_tpu.utils.metrics import REGISTRY


_SEQ = [0]


def _mk_db(n_tables: int = 1, rows: int = 64):
    """Fresh db with uniquely-named tables: stale ScanCaches from other
    tests (held weakly by the occupancy registry until GC) must never
    alias this test's table names in the process-wide inventory."""
    _SEQ[0] += 1
    prefix = f"dt{_SEQ[0]}_"
    db = horaedb_tpu.connect(None)
    for t in range(n_tables):
        db.execute(
            f"CREATE TABLE {prefix}{t} (h string TAG, v double, "
            "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        values = ", ".join(
            f"('h{i % 8}', {float(i)}, {1000 + i})" for i in range(rows)
        )
        db.execute(f"INSERT INTO {prefix}{t} (h, v, ts) VALUES {values}")
    return db, prefix


def _warm(db, prefix: str, t: int = 0, n: int = 3) -> None:
    """Drive the scan cache to a built entry (candidate -> build -> hit)."""
    for _ in range(n):
        db.execute(f"SELECT h, sum(v) FROM {prefix}{t} GROUP BY h")


def _cache(db):
    return db.interpreters.executor.scan_cache


class TestOccupancy:
    def test_inventory_matches_scan_cache_accounting(self):
        """The acceptance invariant: component='column' bytes sum EXACTLY
        to the cache's internal device_bytes — through the obs API and
        through SELECT * FROM system.public.device alike."""
        db, pre = _mk_db()
        try:
            _warm(db, pre)
            cache = _cache(db)
            internal = sum(
                e.device_bytes for e in cache._entries.values()
            )
            assert internal > 0
            rows = cache.snapshot_device()
            col_total = sum(
                r["bytes"] for r in rows if r["component"] == "column"
            )
            assert col_total == internal
            # the SQL face agrees (this cache's rows are a superset-safe
            # filter by its table name; other live caches in the process
            # may contribute rows for other tables)
            out = db.execute(
                "SELECT component, bytes, table_name, dtype, rows "
                "FROM system.public.device"
            ).to_pylist()
            sql_total = sum(
                r["bytes"] for r in out
                if r["component"] == "column" and r["table_name"] == pre + "0"
            )
            assert sql_total == internal
            # dtype + rows columns carry real facts
            vrow = next(
                r for r in out
                if r["table_name"] == pre + "0" and r["dtype"] == "float32"
            )
            assert vrow["rows"] == 64
            # ISSUE 19: the summed bytes are the ENCODED bytes — the
            # layout tuner (on by default) stores series/ts packed, so
            # the inventory carries the encoding per column and at
            # least one column is visibly compressed below 4 B/row
            enc_rows = [
                r for r in cache.snapshot_device()
                if r["component"] == "column"
            ]
            assert all(
                r["encoding"] in ("raw", "bf16", "dict8", "dict16", "delta")
                for r in enc_rows
            )
            packed = [
                r for r in enc_rows
                if r["encoding"] in ("dict8", "dict16", "delta")
            ]
            assert packed, enc_rows
            raw_padded = 4 * next(
                iter(cache._entries.values())
            ).padded_rows  # the bytes this column would cost unencoded
            for r in packed:
                assert r["logical_rows"] > 0
                assert r["bytes"] < raw_padded, r
        finally:
            db.close()

    def test_inventory_tracks_extend_and_rebuild_churn(self):
        """Insert churn: a flush changes the base fingerprint, the entry
        rebuilds, and the inventory keeps agreeing with device_bytes."""
        db, pre = _mk_db()
        try:
            _warm(db, pre)
            db.execute(
                f"INSERT INTO {pre}0 (h, v, ts) VALUES ('h9', 99.0, 5000)"
            )
            db.flush_all()  # base fingerprint changes -> rebuild
            _warm(db, pre)  # candidate -> build -> hit again
            cache = _cache(db)
            internal = sum(e.device_bytes for e in cache._entries.values())
            rows = cache.snapshot_device()
            assert sum(
                r["bytes"] for r in rows if r["component"] == "column"
            ) == internal
            assert any(r["rows"] == 65 for r in rows)
        finally:
            db.close()

    def test_eviction_counted_and_surfaced(self):
        """Budget evictions bump the counter, survive the entry, and the
        evicted table keeps a zero-byte row carrying the count."""
        db, pre = _mk_db(n_tables=2)
        try:
            cache = _cache(db)
            cache.max_entries = 1
            before = REGISTRY.counter(
                "horaedb_device_evictions_total"
            ).value
            _warm(db, pre, 0)
            _warm(db, pre, 1)  # evicts dt0's entry under max_entries=1
            assert cache._evictions.get(pre + "0", 0) >= 1
            assert REGISTRY.counter(
                "horaedb_device_evictions_total"
            ).value > before
            rows = cache.snapshot_device()
            ev = [r for r in rows if r["table_name"] == pre + "0"]
            assert ev and ev[0]["component"] == "evicted"
            assert ev[0]["evictions"] >= 1 and ev[0]["bytes"] == 0
            # resident table's rows carry its (zero) eviction count
            assert all(
                r["evictions"] == 0 for r in rows
                if r["table_name"] == pre + "1"
            )
        finally:
            db.close()

    def test_last_hit_age_and_gauges(self):
        db, pre = _mk_db()
        try:
            _warm(db, pre)
            rows = _cache(db).snapshot_device()
            assert all(
                r["last_hit_age_ms"] >= 0 for r in rows
                if r["component"] == "column"
            )
            inv = device_inventory()  # refreshes the gauges
            totals = occupancy_totals(inv)
            g = REGISTRY.gauge(
                "horaedb_device_resident_bytes",
                labels={"component": "column"},
            )
            assert g.value == totals["column"] > 0
        finally:
            db.close()


class TestKernelTiming:
    def test_sampled_timing_populates_ledger(self):
        """Every dispatch is timed (there is no sampling): each statement
        that dispatched carries a device_ms of its own, the warm ones
        too, and the histogram counts one observation per dispatch."""
        db, pre = _mk_db()
        try:
            def observations():
                return sum(
                    m.count for m in
                    REGISTRY.families()["horaedb_device_dispatch_seconds"]
                )

            _warm(db, pre)
            seen = observations()
            dispatched = 0
            for i in range(9):  # more than the old 1-in-8 sample
                ledger, token = querystats.start_ledger(70 + i, "select ...")
                _warm(db, pre, n=1)
                querystats.finish_ledger(ledger, token, 0.01)
                assert ledger.counts["device_dispatches"] == 1
                assert ledger.counts["device_ms"] > 0
                dispatched += ledger.counts["device_dispatches"]
                # the finalized row carries the fields on the ring
                row = querystats.STATS_STORE.list()[-1]
                assert row["device_dispatches"] == 1
                assert row["device_ms"] > 0
            # (>=: a background plane of an earlier test may dispatch too)
            assert observations() - seen >= dispatched
        finally:
            db.close()

    def test_telemetry_kill_switch(self, monkeypatch):
        monkeypatch.setenv("HORAEDB_DEVICE_TELEMETRY", "0")
        db, pre = _mk_db()
        try:
            ledger, token = querystats.start_ledger(8, "select ...")
            _warm(db, pre)
            querystats.finish_ledger(ledger, token, 0.01)
            assert ledger.counts["device_dispatches"] == 0
            assert ledger.counts["device_ms"] == 0
            assert ledger.counts["compile_hit"] == 0
        finally:
            db.close()

    def test_explain_analyze_always_timed_and_renders_device_line(self):
        """EXPLAIN ANALYZE is timed like every dispatch: its rendered
        ledger carries device_ms and a Device: line whenever a kernel ran
        (acceptance criterion)."""
        db, pre = _mk_db()
        try:
            _warm(db, pre)
            out = db.execute(
                f"EXPLAIN ANALYZE SELECT h, sum(v) FROM {pre}0 GROUP BY h"
            ).to_pylist()
            lines = [r["plan"] for r in out]
            device = [l for l in lines if l.strip().startswith("Device:")]
            assert device, lines
            assert "device_ms=" in device[0]
            assert "compile_hit=" in device[0]
            ledger_line = next(
                l for l in lines if l.strip().startswith("Ledger:")
            )
            assert "device_dispatches=" in ledger_line
            assert "device_ms=" in ledger_line
        finally:
            db.close()

    def test_dispatch_counter_family_ticks(self):
        db, pre = _mk_db()
        try:
            fams = REGISTRY.families()["horaedb_device_dispatch_total"]
            before = sum(m.value for m in fams)
            _warm(db, pre)
            after = sum(
                m.value
                for m in REGISTRY.families()["horaedb_device_dispatch_total"]
            )
            assert after > before
        finally:
            db.close()


class TestCompileAccounting:
    @pytest.fixture(autouse=True)
    def one_kernel(self, monkeypatch):
        """"The same shape again" has to be the same shape: the kernel
        router serves the measured winner of scatter and mxu, each a
        static shape of its own, and on this host their timings are close
        enough for the winner to flip between two statements (the one
        wandering failure of the driver's runs). Offer one impl, and pin
        the device route."""
        from horaedb_tpu.query import kernel_choice

        monkeypatch.setattr(
            kernel_choice, "candidate_kernels", lambda *a, **k: ("scatter",)
        )
        monkeypatch.setenv("HORAEDB_ADAPTIVE_PATH", "0")

    def test_compile_event_fires_once_per_shape(self):
        """A warm process re-running the same query mints ZERO new
        kernel_compile events — compile events fire exactly once per
        static shape bucket."""
        db, pre = _mk_db()
        try:
            _warm(db, pre)  # steady state: entry built, shapes about to settle
            db.execute(f"SELECT h, sum(v) FROM {pre}0 GROUP BY h")
            # forget the process's seen-shape set (NOT the jit cache):
            # the next dispatch re-counts as a compile event, and the one
            # after it must not
            querystats._seen_kernel_keys.clear()
            EVENT_STORE.clear()
            db.execute(f"SELECT h, sum(v) FROM {pre}0 GROUP BY h")
            first = EVENT_STORE.list(kind="kernel_compile")
            assert first, "steady-state dispatch after reset must journal"
            db.execute(f"SELECT h, sum(v) FROM {pre}0 GROUP BY h")
            again = EVENT_STORE.list(kind="kernel_compile")
            assert len(again) == len(first)
            attrs = first[0]["attrs"]
            assert attrs["kernel"] and attrs["shape"]
            assert attrs["wall_ms"] >= 0
        finally:
            db.close()

    def test_compile_hit_marks_ledger_and_counters(self):
        db, pre = _mk_db()
        try:
            _warm(db, pre)
            db.execute(f"SELECT h, sum(v) FROM {pre}0 GROUP BY h")
            querystats._seen_kernel_keys.clear()
            ledger, token = querystats.start_ledger(9, "select ...")
            db.execute(f"SELECT h, sum(v) FROM {pre}0 GROUP BY h")
            querystats.finish_ledger(ledger, token, 0.01)
            assert ledger.counts["compile_hit"] >= 1
            # the next run of the same shape is a compile-cache hit
            stats_before = compile_stats()
            ledger2, token2 = querystats.start_ledger(10, "select ...")
            db.execute(f"SELECT h, sum(v) FROM {pre}0 GROUP BY h")
            querystats.finish_ledger(ledger2, token2, 0.01)
            assert ledger2.counts["compile_hit"] == 0
            stats_after = compile_stats()
            assert sum(v["hits"] for v in stats_after.values()) > sum(
                v["hits"] for v in stats_before.values()
            )
        finally:
            db.close()

    def test_slow_log_renders_device_fields(self):
        """A slow query's log entry carries device_ms / compile_hit at
        the top level — a compile stall reads differently from a slow
        scan at a glance (satellite)."""
        from aiohttp.test_utils import TestClient, TestServer

        from horaedb_tpu.server import create_app

        async def body():
            conn = horaedb_tpu.connect(None)
            app = create_app(conn)
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                app["proxy"].slow_threshold_s = 0.0  # everything is slow
                await client.post("/sql", json={
                    "query": "CREATE TABLE sl (h string TAG, v double, "
                             "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) "
                             "ENGINE=Analytic"})
                await client.post("/sql", json={
                    "query": "INSERT INTO sl (h, v, ts) "
                             "VALUES ('a', 1.0, 100)"})
                for _ in range(3):
                    await client.post("/sql", json={
                        "query": "SELECT h, sum(v) FROM sl GROUP BY h"})
                entries = await (await client.get("/debug/slow_log")).json()
                assert entries
                last = entries[-1]
                assert "device_ms" in last and "compile_hit" in last
                # the full ledger rides along and agrees in kind
                assert "device_dispatches" in last["ledger"]["counts"]
            finally:
                await client.close()
                conn.close()

        asyncio.run(body())


class TestSurfaces:
    def test_debug_device_and_ctl_roundtrip(self, capsys):
        """/debug/device answers the inventory + totals + compile block,
        and `horaectl device` renders the same payload over a real HTTP
        endpoint (satellite acceptance)."""
        from aiohttp.test_utils import TestClient, TestServer

        from horaedb_tpu.server import create_app
        from horaedb_tpu.tools.ctl import cmd_device

        async def body():
            conn = horaedb_tpu.connect(None)
            app = create_app(conn)
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                await client.post("/sql", json={
                    "query": "CREATE TABLE dv (h string TAG, v double, "
                             "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) "
                             "ENGINE=Analytic"})
                await client.post("/sql", json={
                    "query": "INSERT INTO dv (h, v, ts) "
                             "VALUES ('a', 1.0, 100), ('b', 2.0, 200)"})
                for _ in range(3):
                    await client.post("/sql", json={
                        "query": "SELECT h, sum(v) FROM dv GROUP BY h"})
                data = await (await client.get("/debug/device")).json()
                assert data["enabled"] is True
                # every dispatch is timed: no sampling policy to report
                assert "sample_every" not in data
                inv = data["inventory"]
                assert any(
                    r["table_name"] == "dv" and r["component"] == "column"
                    for r in inv
                )
                assert data["totals"]["column"] == sum(
                    r["bytes"] for r in inv if r["component"] == "column"
                )
                assert isinstance(data["compile"], dict)
                # the ctl verb against the same live endpoint (urllib is
                # synchronous: run it off the serving loop)
                ep = f"{client.server.host}:{client.server.port}"
                await asyncio.get_running_loop().run_in_executor(
                    None, cmd_device, ep, None
                )
            finally:
                await client.close()
                conn.close()

        asyncio.run(body())
        out = capsys.readouterr().out
        assert "dv" in out
        assert "totals:" in out
        assert "__series_codes__" in out

    def test_device_table_projection_and_filter(self):
        """system.public.device behaves like any table: projection,
        WHERE, aggregates over every wire's shared query layer."""
        db, pre = _mk_db()
        try:
            _warm(db, pre)
            out = db.execute(
                "SELECT table_name, sum(bytes) AS b "
                "FROM system.public.device "
                "WHERE component = 'column' GROUP BY table_name"
            ).to_pylist()
            mine = [r for r in out if r["table_name"] == pre + "0"]
            assert mine and mine[0]["b"] > 0
        finally:
            db.close()


class TestProfileSelfFrames:
    def test_sample_cpu_filters_own_frames_whole_stack(self):
        """Satellite bugfix: the profiler used to check only the last 2
        frames for utils/profile, so samples caught deeper inside the
        profiler (extract_stack, Counter update) leaked into the hot
        stacks. The whole stack is filtered now."""
        import threading
        import time

        from horaedb_tpu.utils.profile import sample_cpu

        stop = threading.Event()

        def busy():
            while not stop.is_set():
                sum(i * i for i in range(1000))

        t = threading.Thread(target=busy, daemon=True)
        t.start()
        try:
            report = sample_cpu(0.3, interval_s=0.005)
        finally:
            stop.set()
            t.join()
        assert "cpu profile" in report
        assert "utils/profile" not in report
        # the worker thread is still visible
        assert "busy" in report


class TestReviewHardening:
    """Each fix from the single-pass review, regression-pinned."""

    def test_invalidate_forces_gauge_through_throttle(self):
        """An invalidation may be the LAST cache touch for a long time:
        it must push the resident-bytes gauge through the ~1/s refresh
        throttle, never leaving freed bytes on the gauge for the
        recorder to persist."""
        db, pre = _mk_db()
        try:
            _warm(db, pre)
            device_inventory()  # refresh now; arms the throttle window
            g = REGISTRY.gauge(
                "horaedb_device_resident_bytes",
                labels={"component": "column"},
            )
            before = g.value
            assert before > 0
            freed = sum(
                e.device_bytes for e in _cache(db)._entries.values()
            )
            _cache(db).invalidate(pre + "0")  # immediately after refresh
            assert g.value <= before - freed
        finally:
            db.close()

    def test_closed_db_drops_out_of_inventory(self):
        """Connection.close unregisters its scan cache: a closed
        database must stop contributing inventory rows the moment it
        closes, not whenever GC collects it."""
        db, pre = _mk_db()
        _warm(db, pre)
        assert any(
            r["table_name"] == pre + "0" for r in device_inventory()
        )
        db.close()
        assert not any(
            r["table_name"] == pre + "0" for r in device_inventory()
        )

    def test_slow_threshold_couples_to_device_plane(self):
        """A slow-logged query says where its time went at any threshold:
        with every dispatch timed, the slow log's device_ms is measured
        whether the operator's threshold is far above the query or
        dialed down under it afterwards (no always-time rule to couple)."""
        from aiohttp.test_utils import TestClient, TestServer

        from horaedb_tpu.server import create_app

        async def body():
            conn = horaedb_tpu.connect(None)
            app = create_app(conn)
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                proxy = app["proxy"]
                proxy.slow_threshold_s = 3600.0  # nothing is slow yet
                await client.post("/sql", json={
                    "query": "CREATE TABLE st (h string TAG, v double, "
                             "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) "
                             "ENGINE=Analytic"})
                await client.post("/sql", json={
                    "query": "INSERT INTO st (h, v, ts) "
                             "VALUES ('a', 1.0, 100)"})
                select = {"query": "SELECT h, sum(v) FROM st GROUP BY h"}
                for _ in range(3):
                    await client.post("/sql", json=select)
                assert not await (await client.get("/debug/slow_log")).json()
                resp = await client.put("/debug/slow_threshold/0")
                assert (await resp.json())["slow_threshold_s"] == 0.0
                assert proxy.slow_threshold_s == 0.0
                await client.post("/sql", json=select)
                last = (await (await client.get("/debug/slow_log")).json())[-1]
                assert last["ledger"]["counts"]["device_dispatches"] == 1
                assert last["device_ms"] > 0
            finally:
                await client.close()
                conn.close()

        asyncio.run(body())

    def test_close_zeroes_gauges_and_env_knob_still_wins(self, monkeypatch):
        """Second review round: (a) Connection.close force-refreshes the
        resident-bytes gauges (a close is a residency mutation — the
        gauge must not park on freed bytes); (b) the one env knob the
        timing keeps, HORAEDB_DEVICE_TELEMETRY=0, is read per dispatch
        and still means "bare call"."""
        from horaedb_tpu.obs import device as obsdev

        db, pre = _mk_db()
        _warm(db, pre)
        device_inventory()
        g = REGISTRY.gauge(
            "horaedb_device_resident_bytes", labels={"component": "column"}
        )
        mine = sum(e.device_bytes for e in _cache(db)._entries.values())
        before = g.value
        assert before >= mine > 0
        db.close()
        assert g.value <= before - mine
        # (b) a bare call when off, a timed one when on
        for i, (switch, timed) in enumerate((("0", 0), ("1", 1))):
            monkeypatch.setenv("HORAEDB_DEVICE_TELEMETRY", switch)
            ledger, token = querystats.start_ledger(20 + i, "select 1")
            try:
                assert obsdev.timed_dispatch("fused", lambda: 41 + i) == 41 + i
            finally:
                querystats.finish_ledger(ledger, token, 0.0, record_stats=False)
            assert ledger.counts["device_dispatches"] == timed
            assert (ledger.counts["device_ms"] > 0) == bool(timed)

    def test_fused_dist_compile_accounting(self):
        """Third review round: the sharded fused path must account
        compiles like every other dispatch point — a first-sighting
        shard_map compile is a multi-second stall on real chips and the
        slow log/EXPLAIN must be able to name it."""
        import jax
        from jax.sharding import Mesh

        from horaedb_tpu.ops.encoding import build_padded_batch
        from horaedb_tpu.ops.scan_agg import ScanAggSpec
        from horaedb_tpu.parallel import dist_scan_aggregate

        mesh = Mesh(np.array(jax.devices()[:8]), ("shard",))
        rng = np.random.default_rng(3)
        n = 4096
        batch = build_padded_batch(
            rng.integers(0, 5, n).astype(np.int32),
            rng.integers(0, 3, n).astype(np.int32),
            np.ones(n, dtype=bool),
            [rng.normal(size=n).astype(np.float32)],
        )
        spec = ScanAggSpec(
            n_groups=5, n_buckets=3, n_agg_fields=1, segment_impl="scatter"
        ).padded()
        dist_scan_aggregate(mesh, batch, spec)  # settle the jit shape
        querystats._seen_kernel_keys.clear()
        EVENT_STORE.clear()
        ledger, token = querystats.start_ledger(12, "select ...")
        dist_scan_aggregate(mesh, batch, spec)
        querystats.finish_ledger(ledger, token, 0.0, record_stats=False)
        assert ledger.counts["compile_hit"] >= 1
        evs = EVENT_STORE.list(kind="kernel_compile")
        assert any(e["attrs"]["kernel"] == "fused_dist" for e in evs)
        # the repeat is a compile-cache hit, no new event
        dist_scan_aggregate(mesh, batch, spec)
        assert len(EVENT_STORE.list(kind="kernel_compile")) == len(evs)
