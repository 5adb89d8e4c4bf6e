"""Trace metrics + orphan sweep tests."""

import asyncio

import pytest
from aiohttp.test_utils import TestClient, TestServer

import horaedb_tpu
from horaedb_tpu.server import create_app


class TestQueryMetrics:
    def test_executor_records_stages(self):
        db = horaedb_tpu.connect(None)
        db.execute("CREATE TABLE t (h string TAG, v double, ts timestamp KEY)")
        db.execute("INSERT INTO t (h, v, ts) VALUES ('a', 1.0, 1), ('b', 2.0, 2)")
        db.execute("SELECT h, sum(v) FROM t GROUP BY h")
        m = db.interpreters.executor.last_metrics
        assert m["table"] == "t" and m["result_rows"] == 2
        assert m["path"].startswith("device") or m["path"] == "host"
        assert m["total_ms"] > 0
        db.close()

    def test_cache_hit_recorded(self):
        db = horaedb_tpu.connect(None)
        db.execute("CREATE TABLE t (h string TAG, v double, ts timestamp KEY)")
        db.execute("INSERT INTO t (h, v, ts) VALUES ('a', 1.0, 1)")
        sql = "SELECT count(*) AS c FROM t"
        db.execute(sql)  # candidate
        db.execute(sql)  # build
        assert db.interpreters.executor.last_metrics.get("cache") == "build"
        db.execute(sql)  # hit
        assert db.interpreters.executor.last_metrics.get("cache") == "hit"
        db.close()

    def test_debug_queries_endpoint_and_explain_metrics(self):
        async def body(client):
            await client.post("/sql", json={"query": "CREATE TABLE t (h string TAG, v double, ts timestamp KEY)"})
            await client.post("/sql", json={"query": "INSERT INTO t (h, v, ts) VALUES ('a', 1.0, 1)"})
            await client.post("/sql", json={"query": "SELECT h, sum(v) FROM t GROUP BY h"})
            recent = await (await client.get("/debug/queries")).json()
            assert recent and recent[-1]["table"] == "t"
            assert "total_ms" in recent[-1] and "sql" in recent[-1]
            out = await client.post(
                "/sql", json={"query": "EXPLAIN ANALYZE SELECT count(*) FROM t"}
            )
            plan_lines = [r["plan"] for r in (await out.json())["rows"]]
            assert any(l.strip().startswith("Metrics:") for l in plan_lines)

        async def runner():
            conn = horaedb_tpu.connect(None)
            client = TestClient(TestServer(create_app(conn)))
            await client.start_server()
            try:
                await body(client)
            finally:
                await client.close()
                conn.close()

        asyncio.run(runner())


class TestOrphanSweep:
    def test_untracked_sst_removed_at_open(self, tmp_path):
        from horaedb_tpu.common_types import ColumnSchema, DatumKind, RowGroup, Schema
        from horaedb_tpu.engine.instance import Instance
        from horaedb_tpu.utils.object_store import LocalDiskStore

        store = LocalDiskStore(str(tmp_path))
        schema = Schema.build(
            [ColumnSchema("h", DatumKind.STRING, is_tag=True),
             ColumnSchema("v", DatumKind.DOUBLE),
             ColumnSchema("ts", DatumKind.TIMESTAMP)],
            timestamp_column="ts",
        )
        inst = Instance(store)
        t = inst.create_table(0, 1, "t", schema)
        inst.write(t, RowGroup.from_rows(schema, [{"h": "a", "v": 1.0, "ts": 1}]))
        inst.flush_table(t)
        tracked = {h.path for h in t.version.levels.all_files()}
        # crash artifact: an SST that never made the manifest
        store.put("0/1/999.sst", b"garbage")

        inst2 = Instance(store)
        t2 = inst2.open_table(0, 1, "t")
        assert not store.exists("0/1/999.sst")  # swept
        for p in tracked:
            assert store.exists(p)  # real data untouched
        assert len(inst2.read(t2)) == 1


from test_server import with_client  # noqa: E402


class TestProfilingEndpoints:
    def test_cpu_profile(self):
        async def body(client):
            resp = await client.get("/debug/profile/cpu/0.2")
            assert resp.status == 200
            text = await resp.text()
            assert "cpu profile" in text and "hottest frames" in text

        with_client(body)

    def test_heap_profile(self):
        async def body(client):
            resp = await client.get("/debug/profile/heap/0.1")
            assert resp.status == 200
            assert "heap profile" in await resp.text()

        with_client(body)

    def test_log_level_switch(self):
        import logging

        async def body(client):
            before = logging.getLogger().level
            try:
                resp = await client.put("/debug/log_level/debug")
                assert resp.status == 200
                assert logging.getLogger().level == logging.DEBUG
                resp = await client.put("/debug/log_level/bogus")
                assert resp.status == 400
            finally:
                logging.getLogger().setLevel(before)

        with_client(body)


class TestSlowLog:
    def test_slow_queries_recorded(self):
        async def body(client):
            app_proxy = client.server.app["proxy"]
            app_proxy.slow_threshold_s = 0.0  # everything is "slow"
            await client.post("/sql", json={"query": "SHOW TABLES"})
            resp = await client.get("/debug/slow_log")
            entries = await resp.json()
            assert entries and entries[-1]["sql"].startswith("SHOW TABLES")
            assert entries[-1]["elapsed_s"] >= 0

        with_client(body)


class TestAdminFlushAndAuth:
    def test_admin_flush(self):
        async def body(client):
            conn = client.server.app["conn"]
            conn.execute(
                "CREATE TABLE ft (h string TAG, v double, ts timestamp NOT NULL, "
                "TIMESTAMP KEY(ts)) ENGINE=Analytic"
            )
            conn.execute("INSERT INTO ft (h, v, ts) VALUES ('a', 1.0, 100)")
            resp = await client.post("/admin/flush?table=ft")
            assert resp.status == 200
            assert (await resp.json())["flushed"] == ["ft"]
            resp = await client.post("/admin/flush?table=nope")
            assert resp.status == 422

        with_client(body)

    def test_auth_gates_admin_and_debug(self):
        import horaedb_tpu
        from horaedb_tpu.server import create_app
        from aiohttp.test_utils import TestClient, TestServer
        import asyncio

        async def body():
            conn = horaedb_tpu.connect(None)
            app = create_app(conn, auth_token="s3cret")
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                resp = await client.get("/debug/config")
                assert resp.status == 401
                resp = await client.post("/admin/flush")
                assert resp.status == 401
                resp = await client.get(
                    "/debug/config", headers={"Authorization": "Bearer s3cret"}
                )
                assert resp.status == 200
                # the data plane stays open (reference default)
                resp = await client.post("/sql", json={"query": "SHOW TABLES"})
                assert resp.status == 200
            finally:
                await client.close()
                conn.close()

        asyncio.run(body())


class TestSstMetadataTool:
    def test_describe_and_cli(self, tmp_path, capsys):
        import horaedb_tpu
        from horaedb_tpu.tools.sst_metadata import describe, main

        db = horaedb_tpu.connect(str(tmp_path / "d"))
        db.execute(
            "CREATE TABLE st (h string TAG, v double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute("INSERT INTO st (h, v, ts) VALUES ('a', 1.0, 100), ('b', 2.0, 200)")
        db.flush_all()
        db.close()
        ssts = []
        import os

        for root, _, files in os.walk(tmp_path):
            ssts += [os.path.join(root, f) for f in files if f.endswith(".sst")]
        assert ssts
        d = describe(ssts[0])
        assert d["rows"] == 2
        assert d["sst_meta"]["max_sequence"] >= 1
        assert "ts" in d["columns"]
        assert d["row_group_stats"][0]["column_stats"]
        rc = main(["--brief", ssts[0]])
        assert rc == 0
        assert "rows=2" in capsys.readouterr().out


class TestIntrospectionEndpoints:
    def test_wal_stats_and_shards_standalone(self, tmp_path):
        import asyncio

        import horaedb_tpu
        from aiohttp.test_utils import TestClient, TestServer
        from horaedb_tpu.server import create_app

        async def body():
            conn = horaedb_tpu.connect(str(tmp_path / "d"))
            conn.execute(
                "CREATE TABLE iw (h string TAG, v double, ts timestamp NOT NULL, "
                "TIMESTAMP KEY(ts)) ENGINE=Analytic"
            )
            conn.execute("INSERT INTO iw (h, v, ts) VALUES ('a', 1.0, 100)")
            app = create_app(conn)
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                resp = await client.get("/debug/wal_stats")
                stats = await resp.json()
                assert stats["backend"] == "LocalDiskWal"
                assert any(
                    t["log_bytes"] > 0 for t in stats["tables"].values()
                )
                resp = await client.get("/debug/shards")
                assert (await resp.json())["mode"] == "standalone"
            finally:
                await client.close()
                conn.close()

        asyncio.run(body())


class TestRemoteSpans:
    def test_debug_remote_spans_endpoint(self):
        """A remote partial-agg leaves a span (keyed by the origin's
        request id) readable at /debug/remote_spans."""
        from horaedb_tpu.remote.client import RemoteEngineClient
        from horaedb_tpu.remote.service import GrpcServer

        async def runner():
            conn = horaedb_tpu.connect(None)
            conn.execute(
                "CREATE TABLE rs (h string TAG, v double, ts timestamp KEY) "
                "ENGINE=Analytic"
            )
            conn.execute("INSERT INTO rs (h, v, ts) VALUES ('a', 1.0, 1)")
            g = GrpcServer(conn, port=0)
            g.start()
            spec = {
                "predicate": {"time_range": [0, 10**15], "filters": []},
                "exact_filters": [], "device_filters": [],
                "group_tags": ["h"], "bucket_ms": 0, "agg_cols": ["v"],
                "trace": {"request_id": 99},
            }
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(
                None,
                lambda: RemoteEngineClient(
                    f"127.0.0.1:{g.bound_port}"
                ).partial_agg("rs", spec),
            )
            client = TestClient(TestServer(create_app(conn)))
            await client.start_server()
            try:
                spans = (await (await client.get("/debug/remote_spans")).json())[
                    "spans"
                ]
                assert any(s.get("request_id") == 99 for s in spans)
                span = [s for s in spans if s.get("request_id") == 99][-1]
                assert span["table"] == "rs" and span["path"] in ("kernel", "host")
            finally:
                await client.close()
                g.stop()
                conn.close()

        asyncio.run(runner())


class TestEngineMetrics:
    """The round-4 machinery must be visible at /metrics (ROADMAP item:
    observability of the new machinery)."""

    def test_labeled_counters_and_gauge_exposition(self):
        from horaedb_tpu.utils.metrics import Registry

        reg = Registry()
        reg.counter("proc_total", "procs", labels={"kind": "split"}).inc(2)
        reg.counter("proc_total", "procs", labels={"kind": "merge"}).inc()
        reg.counter("other_total", "other").inc()
        g = reg.gauge("depth", "queue depth")
        g.set(5)
        g.dec()
        text = reg.expose()
        # one header per family, samples contiguous, labels rendered
        assert text.count("# TYPE proc_total counter") == 1
        assert 'proc_total{kind="split"} 2.0' in text
        assert 'proc_total{kind="merge"} 1.0' in text
        assert "# TYPE depth gauge" in text and "depth 4.0" in text
        split_i = text.index('kind="split"')
        merge_i = text.index('kind="merge"')
        other_i = text.index("other_total 1.0")
        assert abs(split_i - merge_i) < other_i or other_i < min(split_i, merge_i)

    def test_registry_kind_mismatch_and_label_escaping(self):
        import pytest as _pytest

        from horaedb_tpu.utils.metrics import Registry

        reg = Registry()
        reg.counter("x", "c")
        with _pytest.raises(TypeError):
            reg.gauge("x")
        with _pytest.raises(TypeError):
            reg.histogram("x")
        reg.counter("esc", "e", labels={"kind": 'drop "tmp"\n'}).inc()
        text = reg.expose()
        assert 'kind="drop \\"tmp\\"\\n"' in text

    def test_flush_and_compaction_metrics_recorded(self, tmp_path):
        from horaedb_tpu.utils.metrics import REGISTRY

        flush_rows = REGISTRY.counter("horaedb_flush_rows_total")
        comp_tasks = REGISTRY.counter("horaedb_compaction_tasks_total")
        req = REGISTRY.counter("horaedb_compaction_requests_total")
        before = (flush_rows.value, comp_tasks.value, req.value)
        db = horaedb_tpu.connect(str(tmp_path / "m"))
        db.execute(
            "CREATE TABLE mm (host string TAG, v double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic WITH (segment_duration='1h')"
        )
        for i in range(db.instance.config.compaction_l0_trigger):
            db.execute(f"INSERT INTO mm (host, v, ts) VALUES ('h', {float(i)}, {100 + i})")
            db.catalog.open("mm").flush()
        # Wait for the background merge (close retires handles, so a
        # still-queued merge at close correctly bails without running).
        import time
        t = db.instance.open_tables()[0]
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and t.version.levels.files_at(0):
            time.sleep(0.02)
        db.close()
        assert flush_rows.value > before[0]
        assert req.value > before[2]
        assert comp_tasks.value > before[1]
        assert REGISTRY.histogram("horaedb_flush_duration_seconds").count > 0
        assert REGISTRY.histogram("horaedb_compaction_duration_seconds").count > 0

    def test_procedure_terminal_metrics(self):
        from horaedb_tpu.meta.kv import MemoryKV
        from horaedb_tpu.meta.procedure import ProcedureManager
        from horaedb_tpu.utils.metrics import REGISTRY

        ok = REGISTRY.counter(
            "horaedb_meta_procedure_terminal_total",
            labels={"kind": "noop", "outcome": "finished"},
        )
        fail = REGISTRY.counter(
            "horaedb_meta_procedure_terminal_total",
            labels={"kind": "boom", "outcome": "failed"},
        )
        retries = REGISTRY.counter(
            "horaedb_meta_procedure_retries_total", labels={"kind": "boom"}
        )
        before = (ok.value, fail.value, retries.value)
        def _boom(p):
            raise RuntimeError("x")
        mgr = ProcedureManager(
            MemoryKV(), {"noop": lambda p: None, "boom": _boom},
            max_attempts=2, retry_delay_s=0,
        )
        mgr.run_sync("noop", {})
        mgr.run_sync("boom", {})
        mgr.tick()  # second (terminal) attempt
        assert ok.value == before[0] + 1
        assert fail.value == before[1] + 1
        assert retries.value == before[2] + 2


class TestCompactionDebugSurface:
    def test_debug_compaction_endpoint(self):
        async def run():
            conn = horaedb_tpu.connect(None)
            client = TestClient(TestServer(create_app(conn)))
            await client.start_server()
            r = await client.get("/debug/compaction")
            idle = await r.json()
            assert idle == {
                "pending": [], "running": 0, "closed": False,
                "periodic": False, "backoff": {},
            }
            # trigger background compaction, then the scheduler is live
            await client.post("/sql", json={"query": (
                "CREATE TABLE dc (host string TAG, v double, ts timestamp "
                "NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic "
                "WITH (segment_duration='1h')")})
            for i in range(conn.instance.config.compaction_l0_trigger):
                await client.post("/sql", json={"query":
                    f"INSERT INTO dc (host, v, ts) VALUES ('h', {float(i)}, {100+i})"})
                await client.post("/admin/flush", json={"table": "dc"})
            # The trigger-level flush created the scheduler synchronously,
            # periodic loop included.
            r2 = await client.get("/debug/compaction")
            live = await r2.json()
            assert live["periodic"] and not live["closed"]
            await client.close()
            conn.close()
            assert conn.instance.compaction_stats()["closed"] is True

        asyncio.run(run())


class TestSpanTracing:
    """Hierarchical span tree (ref: trace_metric MetricsCollector): a
    ContextVar-carried tree, cheap no-op outside a trace, bounded rings."""

    def test_span_tree_nesting_and_attrs(self):
        from horaedb_tpu.utils.tracectx import (
            finish_trace, get_request_id, span, start_trace,
        )

        trace, handle = start_trace(1234, "sql", sql="SELECT 1")
        assert get_request_id() == 1234  # legacy flat id still set
        with span("parse") as p:
            p.set(plan_cache="miss")
        with span("execute"):
            with span("scan") as s:
                s.set(rows=10)
        finish_trace(handle)
        root = trace.to_dict()["root"]
        assert root["name"] == "sql" and root["duration_ms"] >= 0
        names = [c["name"] for c in root["children"]]
        assert names == ["parse", "execute"]
        scan = root["children"][1]["children"][0]
        assert scan["name"] == "scan" and scan["attrs"]["rows"] == 10
        assert scan["parent_id"] == root["children"][1]["span_id"]
        assert get_request_id() is None  # context restored

    def test_no_trace_is_cheap_noop(self):
        from horaedb_tpu.utils.tracectx import current_span, span

        assert current_span() is None
        with span("anything", x=1) as s:
            s.set(y=2)  # absorbed, nothing recorded anywhere
        assert current_span() is None

    def test_span_outside_a_trace_allocates_nothing(self, monkeypatch):
        """Outside a trace ``span()`` hands out the one shared null span:
        no Span, no id, and no profiler annotation is made."""
        from horaedb_tpu.utils import tracectx

        made = []
        monkeypatch.setattr(
            tracectx, "_annotation", lambda name: made.append(name)
        )
        monkeypatch.setattr(
            tracectx, "Span", lambda *a, **k: made.append("Span")
        )
        for name in ("prepare", "dispatch", "device_wait"):
            with tracectx.span(name, rows=1) as s:
                assert s is tracectx._NULL_SPAN
                s.set(more=2).finish()
        assert made == []

    def test_spans_and_roots_enter_profiler_annotations(self, monkeypatch):
        """One clock: every span of a trace, the root too, is entered as
        ``hdb:<name>`` for exactly its lifetime (inert until a profiler
        session runs; here a recorder stands in for the profiler)."""
        import jax  # noqa: F401  the annotation exists once jax is loaded

        from horaedb_tpu.utils import tracectx

        log = []

        class Recorder:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                log.append(("enter", self.name))

            def __exit__(self, *exc):
                log.append(("exit", self.name))

        monkeypatch.setattr(tracectx, "_TraceAnnotation", Recorder)
        trace, handle = tracectx.start_trace(77, "sql")
        with tracectx.span("execute"):
            with tracectx.span("dispatch", kernel="cached_packed"):
                pass
        tracectx.finish_trace(handle, record=False)
        with tracectx.owned_trace("write", route="ingest") as root:
            root.set(rows=1)
        assert log == [
            ("enter", "hdb:sql"), ("enter", "hdb:execute"),
            ("enter", "hdb:dispatch"), ("exit", "hdb:dispatch"),
            ("exit", "hdb:execute"), ("exit", "hdb:sql"),
            ("enter", "hdb:write"), ("exit", "hdb:write"),
        ]

    def test_served_cached_aggregate_span_tree(self, monkeypatch):
        """The served read path, stage by stage: one cached aggregate
        over /sql holds admission_wait under ``sql`` and lane_wait,
        prepare, upload, dispatch, device_wait, fetch, assemble in that
        order under ``sql/execute``; the tree still reconciles; and the
        handler's own ``http_sql`` root answers to the same request id,
        which the response carries."""
        from horaedb_tpu.obs import profile
        from horaedb_tpu.obs.profile import UNTRACKED, ProfileAggregator
        from horaedb_tpu.utils.tracectx import TRACE_STORE

        monkeypatch.setenv("HORAEDB_ADAPTIVE_PATH", "0")  # device-first

        async def body(client):
            await client.post("/sql", json={"query":
                "CREATE TABLE st (h string TAG, v double, ts timestamp KEY)"})
            values = ", ".join(
                f"('h{i % 4}', {float(i)}, {1000 + i})" for i in range(64)
            )
            await client.post("/sql", json={"query":
                f"INSERT INTO st (h, v, ts) VALUES {values}"})
            select = {"query": "SELECT h, sum(v) AS s FROM st GROUP BY h"}
            for _ in range(4):  # candidate, build, hit, hit
                resp = await client.post("/sql", json=select)
                assert resp.status == 200
            rid = int(resp.headers["X-HoraeDB-Request-Id"])
            recent = await (await client.get("/debug/queries")).json()
            assert recent[-1]["request_id"] == rid
            entry = await (await client.get(f"/debug/trace/{rid}")).json()
            root = entry["root"]
            assert root["name"] == "sql"
            top = [c["name"] for c in root["children"]]
            assert top == ["parse_plan", "admission_wait", "execute"]
            wait, execute = root["children"][1:]
            # the class follows the shape's cost history (a slow host makes
            # the build statement "expensive"): any class, nobody queued
            assert wait["attrs"]["queued"] == 0
            assert wait["attrs"]["class"] == execute["attrs"]["admission"]
            stages = [c["name"] for c in execute["children"]]
            assert stages == [
                "lane_wait", "prepare", "upload", "dispatch", "device_wait",
                "fetch", "assemble",
            ]
            assert all(
                c["parent_id"] == execute["span_id"]
                for c in execute["children"]
            )
            by_name = {c["name"]: c for c in execute["children"]}
            assert by_name["prepare"]["attrs"] == {"cache": "hit", "rows": 64}
            assert by_name["upload"]["attrs"] == {"selective": False}
            dispatch = by_name["dispatch"]["attrs"]
            assert dispatch["kernel"] == "cached_packed"
            assert dispatch["program"] == "cached_scan_" + dispatch["impl"]
            assert by_name["fetch"]["attrs"]["bytes"] > 0
            assert by_name["assemble"]["attrs"] == {"rows": 4}
            # root_ms == sum of non-root exclusive + untracked, still
            agg = ProfileAggregator()
            agg.fold(rid, root, route="query", shape="s")
            rows = {r["path"]: r for r in agg.list()}
            assert "sql/execute/device_wait" in rows
            non_root = sum(
                r["exclusive_ms"] for p, r in rows.items() if "/" in p
            )
            assert non_root == pytest.approx(root["duration_ms"], abs=1e-6)
            assert f"sql/{UNTRACKED}" in rows
            # the handler's root: folded, and not in the /debug/trace ring
            assert profile.flush(5.0)
            prof = (await (await client.get(
                "/debug/profile?path=http_sql&route=http"
            )).json())["profile"]
            paths = {r["path"]: r for r in prof}
            assert set(paths) == {
                "http_sql", "http_sql/accept", "http_sql/handle",
                "http_sql/handle/rows", "http_sql/encode",
                f"http_sql/{UNTRACKED}",
            }
            assert all(r["last_trace_id"] == rid for r in paths.values())
            assert not any(
                t["name"] == "http_sql" for t in TRACE_STORE.list()
            )

        with_client(body)

    def test_write_path_has_a_trace_root(self):
        """POST /write opens the ``write`` root, so the engine's write
        spans are recorded on the HTTP write path and the ingest plane
        has profile rows."""
        from horaedb_tpu.obs import profile
        from horaedb_tpu.utils.tracectx import TRACE_STORE

        async def body(client):
            await client.post("/sql", json={"query":
                "CREATE TABLE wr (h string TAG, v double, ts timestamp KEY)"})
            resp = await client.post("/write", json={
                "table": "wr", "rows": [{"h": "a", "v": 1.0, "ts": 7}]})
            assert (await resp.json())["affected_rows"] == 1
            newest = TRACE_STORE.list()[0]
            assert newest["name"] == "write"
            root = TRACE_STORE.get(newest["trace_id"])["root"]
            group = [c for c in root["children"] if c["name"] == "write_group"]
            assert group and any(
                c["name"] == "memtable_write" for c in group[0]["children"]
            )
            assert profile.flush(5.0)
            prof = (await (await client.get(
                "/debug/profile?path=write&route=ingest"
            )).json())["profile"]
            mine = {r["path"] for r in prof if r["shape"] == "insert wr"}
            assert {"write", "write/write_group"} <= mine

        with_client(body)

    def test_children_bounded(self):
        from horaedb_tpu.utils.tracectx import (
            MAX_CHILDREN, finish_trace, span, start_trace,
        )

        trace, handle = start_trace(1, "flood")
        for i in range(MAX_CHILDREN + 7):
            with span(f"s{i}"):
                pass
        finish_trace(handle)
        root = trace.to_dict()["root"]
        assert len(root["children"]) == MAX_CHILDREN
        assert root["dropped_children"] == 7

    def test_graft_marks_remote_origin(self):
        from horaedb_tpu.utils.tracectx import (
            finish_trace, graft, start_trace,
        )

        trace, handle = start_trace(2, "sql")
        graft(
            {"name": "remote_partial_agg", "duration_ms": 1.5,
             "attrs": {"path": "kernel"},
             "children": [{"name": "scan", "duration_ms": 1.0}]},
            endpoint="10.0.0.2:8831",
        )
        finish_trace(handle)
        r = trace.to_dict()["root"]["children"][0]
        assert r["attrs"]["origin"] == "remote"
        assert r["attrs"]["endpoint"] == "10.0.0.2:8831"
        assert r["duration_ms"] == 1.5
        # grafted child keeps remote marking and renumbered parentage
        assert r["children"][0]["parent_id"] == r["span_id"]

    def test_trace_store_rings_capped(self):
        from horaedb_tpu.utils.tracectx import Trace, TraceStore

        store = TraceStore(recent=4, slow=8)
        for i in range(20):
            store.record(Trace(i, "sql"), slow=(i % 2 == 0))
        assert len(store._recent) == 4 and len(store._slow) == 8
        # slow traces stay findable after falling out of the recent ring
        assert store.get(10) is not None
        assert store.get(1) is None  # odd (not slow) + evicted

    def test_http_trace_endpoints_and_slow_log_tree(self):
        async def body(client):
            client.server.app["proxy"].slow_threshold_s = 0.0
            await client.post("/sql", json={"query":
                "CREATE TABLE tt (h string TAG, v double, ts timestamp KEY)"})
            await client.post("/sql", json={"query":
                "INSERT INTO tt (h, v, ts) VALUES ('a', 1.0, 1)"})
            await client.post("/sql", json={"query":
                "SELECT h, sum(v) FROM tt GROUP BY h"})
            recent = await (await client.get("/debug/queries")).json()
            rid = recent[-1]["request_id"]
            listing = await (await client.get("/debug/trace")).json()
            assert any(t["trace_id"] == rid for t in listing["traces"])
            resp = await client.get(f"/debug/trace/{rid}")
            assert resp.status == 200
            tree = await resp.json()
            assert tree["trace_id"] == rid
            names = {c["name"] for c in tree["root"]["children"]}
            assert "parse_plan" in names and "execute" in names
            assert (await client.get("/debug/trace/999999")).status == 404
            # the slow log carries the same span tree per request
            slow = await (await client.get("/debug/slow_log")).json()
            assert slow[-1]["trace"]["root"]["name"] == "sql"

        with_client(body)

    def test_explain_analyze_renders_span_tree(self):
        from horaedb_tpu.utils.tracectx import TRACE_STORE

        db = horaedb_tpu.connect(None)
        db.execute("CREATE TABLE ea (h string TAG, v double, ts timestamp KEY)")
        db.execute("INSERT INTO ea (h, v, ts) VALUES ('a', 1.0, 1)")
        lines = [
            r["plan"]
            for r in db.execute(
                "EXPLAIN ANALYZE SELECT h, sum(v) FROM ea GROUP BY h"
            ).to_pylist()
        ]
        text = "\n".join(lines)
        assert "Trace: request_id=" in text
        rid = text.split("Trace: request_id=")[1].splitlines()[0].strip()
        assert "analyze" in text
        # same tree retrievable from the store (what /debug/trace serves)
        entry = TRACE_STORE.get(rid)
        assert entry is not None
        assert entry["root"]["children"][0]["name"] == "analyze"
        db.close()


class TestQueryLedger:
    """Per-query cost ledger mechanics (utils/querystats)."""

    def test_record_noop_outside_request(self):
        from horaedb_tpu.utils import querystats

        assert querystats.current_ledger() is None
        querystats.record(scan_rows=5)  # absorbed, nothing anywhere
        querystats.set_route("host")
        querystats.merge_remote({"counts": {"scan_rows": 3}})
        assert querystats.current_ledger() is None

    def test_ledger_accumulates_and_finalizes(self):
        from horaedb_tpu.utils.querystats import (
            STATS_STORE, finish_ledger, record, set_route, start_ledger,
        )

        ledger, token = start_ledger(42, "SELECT 1")
        record(scan_rows=10, sst_read=2)
        record(scan_rows=5)
        set_route("device")
        # a remote owner's shipped ledger folds in (numeric fields add)
        ledger.merge_remote({"route": "host", "counts": {"scan_rows": 7, "bogus": 1}})
        finish_ledger(ledger, token, 0.25)
        row = STATS_STORE.list()[-1]
        assert row["request_id"] == 42
        assert row["scan_rows"] == 22 and row["sst_read"] == 2
        assert row["route"] == "device"  # remote route never wins
        assert row["duration_ms"] == 250.0

    def test_serving_ledger_ships_and_never_records(self):
        from horaedb_tpu.utils.querystats import (
            STATS_STORE, record, serving_ledger,
        )

        before = len(STATS_STORE.list())
        sl = serving_ledger(7)
        with sl:
            record(scan_rows=99, remote_bytes=12)
        assert len(STATS_STORE.list()) == before  # owner ring untouched
        wire = sl.wire
        assert wire["counts"]["scan_rows"] == 99

    def test_explain_analyze_renders_ledger(self):
        db = horaedb_tpu.connect(None)
        db.execute("CREATE TABLE el (h string TAG, v double, ts timestamp KEY)")
        db.execute("INSERT INTO el (h, v, ts) VALUES ('a', 1.0, 1)")
        lines = [
            r["plan"]
            for r in db.execute(
                "EXPLAIN ANALYZE SELECT h, sum(v) FROM el GROUP BY h"
            ).to_pylist()
        ]
        ledger_lines = [l for l in lines if l.strip().startswith("Ledger:")]
        assert ledger_lines, lines
        assert "route=" in ledger_lines[0] and "scan_rows=1" in ledger_lines[0]
        db.close()

    def test_slow_log_carries_ledger(self):
        async def body(client):
            client.server.app["proxy"].slow_threshold_s = 0.0
            await client.post("/sql", json={"query":
                "CREATE TABLE sl (h string TAG, v double, ts timestamp KEY)"})
            await client.post("/sql", json={"query":
                "INSERT INTO sl (h, v, ts) VALUES ('a', 1.0, 1)"})
            await client.post("/sql", json={"query":
                "SELECT h, sum(v) FROM sl GROUP BY h"})
            slow = await (await client.get("/debug/slow_log")).json()
            entry = slow[-1]
            assert entry["ledger"]["route"] in (
                "device", "device-cached", "device-dist", "device-partial",
                "dist-plan", "host",
            )
            assert entry["ledger"]["counts"]["scan_rows"] >= 1
            # /debug/query_stats serves the same finalized rows
            qs = await (await client.get("/debug/query_stats")).json()
            assert any(
                q["request_id"] == entry["request_id"] for q in qs["queries"]
            )

        with_client(body)


class TestLabeledHistogram:
    def test_per_labelset_exposition(self):
        from horaedb_tpu.utils.metrics import Registry

        reg = Registry()
        h1 = reg.histogram("req_seconds", "latency", labels={"protocol": "mysql"})
        h2 = reg.histogram("req_seconds", "latency", labels={"protocol": "pg"})
        assert reg.histogram("req_seconds", labels={"protocol": "mysql"}) is h1
        h1.observe(0.002)
        h1.observe(0.2)
        h2.observe(5.0)
        text = reg.expose()
        # ONE family header, per-labelset bucket/sum/count lines
        assert text.count("# TYPE req_seconds histogram") == 1
        assert 'req_seconds_bucket{protocol="mysql",le="+Inf"} 2' in text
        assert 'req_seconds_bucket{protocol="pg",le="+Inf"} 1' in text
        assert 'req_seconds_count{protocol="mysql"} 2' in text
        assert 'req_seconds_sum{protocol="pg"} 5.0' in text
        # bucket cumulative counts stay correct per labelset
        assert 'req_seconds_bucket{protocol="mysql",le="0.005"} 1' in text

    def test_histogram_labelset_kind_mismatch(self):
        import pytest as _pytest

        from horaedb_tpu.utils.metrics import Registry

        reg = Registry()
        reg.histogram("x_seconds", labels={"a": "1"})
        with _pytest.raises(TypeError):
            reg.counter("x_seconds", labels={"a": "1"})


class TestPrometheusContentType:
    def test_metrics_exposition_content_type(self):
        async def body(client):
            resp = await client.get("/metrics")
            assert resp.status == 200
            assert resp.headers["Content-Type"] == (
                "text/plain; version=0.0.4; charset=utf-8"
            )
            assert "horaedb_queries_total" in await resp.text()

        with_client(body)


class TestRouterProbeCounter:
    """``horaedb_router_probes_total{router=path|kernel}``: the probe
    schedule's counter (query/path_router.py)."""

    LINES = {
        r: f'horaedb_router_probes_total{{router="{r}"}}'
        for r in ("path", "kernel")
    }

    def test_both_labelsets_export_zero_from_process_start(self):
        """A window without probes must read 0, not a missing series: both
        labelsets exist once the module is imported, before any probe."""
        import os
        import subprocess
        import sys

        out = subprocess.run(
            [sys.executable, "-c",
             "import horaedb_tpu.query.path_router\n"
             "from horaedb_tpu.utils.metrics import REGISTRY\n"
             "print(REGISTRY.expose())"],
            capture_output=True, text=True, timeout=120,
            cwd=os.path.join(os.path.dirname(__file__), ".."),
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert out.returncode == 0, out.stderr
        for line in self.LINES.values():
            assert f"{line} 0.0" in out.stdout.splitlines()

    def test_metrics_counts_one_per_hand_out(self):
        from horaedb_tpu.query.kernel_choice import KernelRouter
        from horaedb_tpu.query.path_router import PROBE_EVERY, PathRouter

        def read(text):
            return {
                r: float(next(
                    ln for ln in text.splitlines() if ln.startswith(line)
                ).split()[-1])
                for r, line in self.LINES.items()
            }

        async def body(client):
            before = read(await (await client.get("/metrics")).text())
            path, kernel = PathRouter(), KernelRouter()
            for kind in ("device", "device", "host"):
                path.record("k", kind, 1.0 if kind == "device" else 2.0)
            for impl in ("scatter", "scatter", "mxu", "mxu"):
                kernel.record("k", impl, 1.0 if impl == "scatter" else 2.0)
            handed = {"path": [], "kernel": []}
            for _ in range(2 * PROBE_EVERY + 1):
                handed["path"].append(path.choose("k"))
                path.record("k", handed["path"][-1], 1.0)
                handed["kernel"].append(
                    kernel.choose("k", "scatter", ("scatter", "mxu"))[0]
                )
                kernel.record("k", handed["kernel"][-1], 1.0)
            assert handed["path"].count("host") == 1
            assert handed["kernel"].count("mxu") == 1
            after = read(await (await client.get("/metrics")).text())
            assert {r: after[r] - before[r] for r in after} == {
                "path": 1.0, "kernel": 1.0,
            }

        with_client(body)


class TestWireProtocolLatency:
    """Front-end parity: MySQL and PostgreSQL record request-latency
    histograms in the same labeled family the HTTP path uses."""

    def test_mysql_and_pg_request_histograms(self):
        import socket

        from horaedb_tpu.server.http import latency_histogram
        from horaedb_tpu.server.mysql import MysqlServer
        from horaedb_tpu.server.postgres import PostgresServer
        from test_wire_protocols import MyClient, PgClient, gateway_for

        MY_LAT = latency_histogram("mysql")
        PG_LAT = latency_histogram("postgres")

        conn = horaedb_tpu.connect(None)
        conn.execute(
            "CREATE TABLE wl (host string TAG, v double, ts timestamp "
            "NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        conn.execute("INSERT INTO wl (host, v, ts) VALUES ('a', 1.5, 1000)")
        before = (MY_LAT.count, PG_LAT.count)

        def my_client(port):
            s = socket.create_connection(("127.0.0.1", port), timeout=10)
            c = MyClient(s)
            c.handshake()
            assert c.query("SELECT host FROM wl")[0] == "rows"
            s.close()

        def pg_client(port):
            s = socket.create_connection(("127.0.0.1", port), timeout=10)
            c = PgClient(s)
            c.startup()
            names, rows, complete, err = c.query("SELECT host FROM wl")
            assert err is None and rows == [["a"]]
            s.close()

        async def body():
            gw = gateway_for(conn)
            my = MysqlServer(gw, port=0)
            pg = PostgresServer(gw, port=0)
            await my.start()
            await pg.start()
            loop = asyncio.get_running_loop()
            try:
                await loop.run_in_executor(None, my_client, my.port)
                await loop.run_in_executor(None, pg_client, pg.port)
            finally:
                await my.stop()
                await pg.stop()

        try:
            asyncio.run(body())
        finally:
            conn.close()
        assert MY_LAT.count > before[0]
        assert PG_LAT.count > before[1]
        from horaedb_tpu.utils.metrics import REGISTRY

        text = REGISTRY.expose()
        assert 'horaedb_request_duration_seconds_count{protocol="mysql"}' in text
        assert 'horaedb_request_duration_seconds_count{protocol="postgres"}' in text


class TestMetricsNameLint:
    """Metric-name convention lint (satellite): every live family must be
    horaedb_-prefixed with a unit suffix — prevents the name drift the
    reference crates suffer from."""

    # _ratio: unitless level-valued gauges (e.g. SLO burn rates) — a
    # counter-suffix (_total) on a gauge would invite rate() on a level
    SUFFIXES = ("_seconds", "_bytes", "_total", "_rows", "_ratio")

    def test_registry_families_follow_convention(self, tmp_path):
        import re

        from horaedb_tpu.utils.metrics import REGISTRY

        # Representative workload: WAL write + flush + query, so the
        # engine/WAL/query families are all live before the walk.
        db = horaedb_tpu.connect(str(tmp_path / "lint"))
        db.execute(
            "CREATE TABLE lint (h string TAG, v double, ts timestamp "
            "NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        db.execute("INSERT INTO lint (h, v, ts) VALUES ('a', 1.0, 100)")
        db.flush_all()
        db.execute("SELECT h, sum(v) FROM lint GROUP BY h")
        db.close()

        pat = re.compile(r"^horaedb_[a-z0-9_]+$")
        bad = []
        for family in REGISTRY.families():
            if not pat.match(family) or not family.endswith(self.SUFFIXES):
                bad.append(family)
        assert not bad, f"metric families violating naming convention: {bad}"

    def test_ledger_fields_map_to_columns_metrics_and_docs(self):
        """PR-2 lint extension: every ledger field must have (a) a
        system.public.query_stats column, (b) a live horaedb_* metric
        family following the naming convention, and (c) a mention in
        docs/OBSERVABILITY.md — a new cost counter cannot land silently."""
        import os
        import re

        from horaedb_tpu.table_engine.system import _QUERY_STATS_SCHEMA
        from horaedb_tpu.utils.metrics import REGISTRY
        from horaedb_tpu.utils.querystats import (
            LEDGER_FIELDS,
            finish_ledger,
            metric_name,
            start_ledger,
        )

        # finalize one synthetic ledger so every family is live
        ledger, token = start_ledger(0, "lint")
        ledger.add(**{f: 1 for f in LEDGER_FIELDS})
        ledger.set_route("host")
        finish_ledger(ledger, token, 0.001)

        columns = {c.name for c in _QUERY_STATS_SCHEMA.columns}
        docs = open(
            os.path.join(os.path.dirname(__file__), "..", "docs", "OBSERVABILITY.md")
        ).read()
        families = set(REGISTRY.families())
        pat = re.compile(r"^horaedb_[a-z0-9_]+$")
        missing = []
        for field in LEDGER_FIELDS:
            fam = metric_name(field)
            if field not in columns:
                missing.append(f"{field}: no query_stats column")
            if fam not in families:
                missing.append(f"{field}: metric family {fam} not registered")
            if not pat.match(fam) or not fam.endswith(self.SUFFIXES):
                missing.append(f"{field}: family {fam} violates naming lint")
            if f"`{field}`" not in docs:
                missing.append(f"{field}: undocumented in docs/OBSERVABILITY.md")
        assert "horaedb_query_route_total" in families
        assert not missing, missing

    def test_admission_families_map_to_workload_rows_and_docs(self):
        """PR-3 lint extension (same contract): every horaedb_admission_*
        family declared in wlm.ADMISSION_METRIC_FAMILIES must be (a)
        registered live, (b) convention-clean, (c) visible as rows of
        system.public.workload, and (d) documented in docs/WORKLOAD.md —
        and no stray horaedb_admission_* family may exist outside the
        declared registry."""
        import os
        import re

        from horaedb_tpu.table_engine.system import WorkloadTable
        from horaedb_tpu.utils.metrics import REGISTRY
        from horaedb_tpu.wlm import ADMISSION_METRIC_FAMILIES, WorkloadManager

        mgr = WorkloadManager()  # at least one live manager for gauges
        try:
            rows = WorkloadTable()._materialize()
            row_names = set(rows.columns["name"])
        finally:
            mgr.close()
        docs = open(
            os.path.join(os.path.dirname(__file__), "..", "docs", "WORKLOAD.md")
        ).read()
        families = set(REGISTRY.families())
        pat = re.compile(r"^horaedb_[a-z0-9_]+$")
        missing = []
        for fam in ADMISSION_METRIC_FAMILIES:
            if fam not in families:
                missing.append(f"{fam}: not registered")
            if not pat.match(fam) or not fam.endswith(self.SUFFIXES):
                missing.append(f"{fam}: violates naming lint")
            if fam not in row_names:
                missing.append(f"{fam}: no system.public.workload row")
            if f"`{fam}`" not in docs:
                missing.append(f"{fam}: undocumented in docs/WORKLOAD.md")
        for fam in families:
            if fam.startswith("horaedb_admission_") and \
                    fam not in ADMISSION_METRIC_FAMILIES:
                missing.append(f"{fam}: live but undeclared in registry")
        # the wlm ledger fields ride the PR-2 lint automatically; pin the
        # workload doc mention too so the contract is discoverable
        for field in ("admission_wait_seconds", "dedup_followers",
                      "dedup_follower"):
            if f"`{field}`" not in docs:
                missing.append(f"{field}: undocumented in docs/WORKLOAD.md")
        assert not missing, missing

    def test_flush_pipeline_families_declared_and_documented(self):
        """PR-4 lint extension (same contract as the admission registry):
        every flush-pipeline family declared in
        engine.flush_scheduler.FLUSH_PIPELINE_METRIC_FAMILIES must be (a)
        registered live, (b) convention-clean, and (c) documented in
        docs/OBSERVABILITY.md — and no stray horaedb_flush_* /
        horaedb_write_stall_* family may exist outside the declared list.
        The pipeline's config knobs must be documented in
        docs/WORKLOAD.md."""
        import os
        import re

        # Importing these registers every declared family (schedulers and
        # flush register at module import; no workload needed).
        import horaedb_tpu.engine.flush  # noqa: F401
        import horaedb_tpu.engine.instance  # noqa: F401
        from horaedb_tpu.engine.flush_scheduler import (
            FLUSH_PIPELINE_METRIC_FAMILIES,
        )
        from horaedb_tpu.utils.metrics import REGISTRY

        here = os.path.dirname(__file__)
        docs = open(os.path.join(here, "..", "docs", "OBSERVABILITY.md")).read()
        wdocs = open(os.path.join(here, "..", "docs", "WORKLOAD.md")).read()
        families = set(REGISTRY.families())
        pat = re.compile(r"^horaedb_[a-z0-9_]+$")
        missing = []
        for fam in FLUSH_PIPELINE_METRIC_FAMILIES:
            if fam not in families:
                missing.append(f"{fam}: not registered")
            if not pat.match(fam) or not fam.endswith(self.SUFFIXES):
                missing.append(f"{fam}: violates naming lint")
            if f"`{fam}`" not in docs:
                missing.append(f"{fam}: undocumented in docs/OBSERVABILITY.md")
        for fam in families:
            if (
                fam.startswith("horaedb_flush_")
                or fam.startswith("horaedb_write_stall")
            ) and fam not in FLUSH_PIPELINE_METRIC_FAMILIES:
                missing.append(f"{fam}: live but undeclared in registry")
        # The backpressure/scheduler knobs are operator surface: pin the
        # WORKLOAD.md mention so the contract is discoverable.
        for knob in (
            "background_flush", "flush_workers", "compaction_workers",
            "write_stall_immutable_count", "write_stall_immutable_bytes",
            "write_stall_deadline",
        ):
            if f"`{knob}`" not in wdocs:
                missing.append(f"{knob}: undocumented in docs/WORKLOAD.md")
        assert not missing, missing

    def test_agg_kernel_family_declared_and_documented(self):
        """PR-6 lint extension (same contract as the admission/flush
        registries): the horaedb_agg_kernel_total family declared in
        querystats.AGG_KERNEL_METRIC_FAMILIES must be (a) registered
        live with every SEGMENT_KERNEL_LABELS label, (b)
        convention-clean, (c) documented in docs/OBSERVABILITY.md along
        with the `kernel` query_stats column — and no stray
        horaedb_agg_* family may exist outside the declared registry.
        The router/kernel knobs are operator surface: pinned to
        docs/WORKLOAD.md."""
        import os
        import re

        from horaedb_tpu.table_engine.system import _QUERY_STATS_SCHEMA
        from horaedb_tpu.utils.metrics import REGISTRY
        from horaedb_tpu.utils.querystats import (
            AGG_KERNEL_METRIC_FAMILIES,
            SEGMENT_KERNEL_LABELS,
        )

        here = os.path.dirname(__file__)
        docs = open(os.path.join(here, "..", "docs", "OBSERVABILITY.md")).read()
        wdocs = open(os.path.join(here, "..", "docs", "WORKLOAD.md")).read()
        families = set(REGISTRY.families())
        pat = re.compile(r"^horaedb_[a-z0-9_]+$")
        exposed = REGISTRY.expose()
        missing = []
        for fam in AGG_KERNEL_METRIC_FAMILIES:
            if fam not in families:
                missing.append(f"{fam}: not registered")
            if not pat.match(fam) or not fam.endswith(self.SUFFIXES):
                missing.append(f"{fam}: violates naming lint")
            if f"`{fam}`" not in docs:
                missing.append(f"{fam}: undocumented in docs/OBSERVABILITY.md")
        for kernel in SEGMENT_KERNEL_LABELS:
            if f'kernel="{kernel}"' not in exposed:
                missing.append(f"label kernel={kernel}: not eagerly registered")
        for fam in families:
            if fam.startswith("horaedb_agg_") and \
                    fam not in AGG_KERNEL_METRIC_FAMILIES:
                missing.append(f"{fam}: live but undeclared in registry")
        # the kernel column + agg_segments field ride the query_stats
        # schema; the `kernel` column is not a LEDGER_FIELD (string, not
        # numeric) so pin it explicitly
        columns = {c.name for c in _QUERY_STATS_SCHEMA.columns}
        if "kernel" not in columns:
            missing.append("kernel: no query_stats column")
        if "`kernel`" not in docs:
            missing.append("kernel: undocumented in docs/OBSERVABILITY.md")
        if "`HORAEDB_CACHE_DTYPE`" not in wdocs:
            missing.append("HORAEDB_CACHE_DTYPE: undocumented in docs/WORKLOAD.md")
        assert not missing, missing

    def test_raw_scan_family_declared_and_documented(self):
        """PR-7 lint extension (same contract as the agg-kernel
        registry): the horaedb_raw_scan_total family declared in
        querystats.RAW_SCAN_METRIC_FAMILIES must be (a) registered live
        with every RAW_SCAN_PATHS label, (b) convention-clean, (c)
        documented in docs/OBSERVABILITY.md — and no stray
        horaedb_raw_* family may exist outside the declared registry.
        The raw knobs are operator surface: pinned to docs/WORKLOAD.md.
        (The `raw_rows_returned` ledger field rides the PR-2 lint
        automatically: column + family + docs mention.)"""
        import os
        import re

        from horaedb_tpu.utils.metrics import REGISTRY
        from horaedb_tpu.utils.querystats import (
            RAW_SCAN_METRIC_FAMILIES,
            RAW_SCAN_PATHS,
        )

        here = os.path.dirname(__file__)
        docs = open(os.path.join(here, "..", "docs", "OBSERVABILITY.md")).read()
        wdocs = open(os.path.join(here, "..", "docs", "WORKLOAD.md")).read()
        families = set(REGISTRY.families())
        pat = re.compile(r"^horaedb_[a-z0-9_]+$")
        exposed = REGISTRY.expose()
        missing = []
        for fam in RAW_SCAN_METRIC_FAMILIES:
            if fam not in families:
                missing.append(f"{fam}: not registered")
            if not pat.match(fam) or not fam.endswith(self.SUFFIXES):
                missing.append(f"{fam}: violates naming lint")
            if f"`{fam}`" not in docs:
                missing.append(f"{fam}: undocumented in docs/OBSERVABILITY.md")
        for path in RAW_SCAN_PATHS:
            if f'path="{path}"' not in exposed:
                missing.append(f"label path={path}: not eagerly registered")
        for fam in families:
            if fam.startswith("horaedb_raw_") and \
                    fam not in RAW_SCAN_METRIC_FAMILIES:
                missing.append(f"{fam}: live but undeclared in registry")
        for knob in ("HORAEDB_RAW_DEVICE", "HORAEDB_RAW_MAX_ROWS"):
            if f"`{knob}`" not in wdocs:
                missing.append(f"{knob}: undocumented in docs/WORKLOAD.md")
        assert not missing, missing

    def test_batch_families_declared_and_documented(self):
        """PR-13 lint extension (same contract as the admission/raw
        registries): every horaedb_batch_* family declared in
        wlm.BATCH_METRIC_FAMILIES must be (a) registered live (with
        every kind/size label eagerly present), (b) convention-clean,
        (c) documented in docs/WORKLOAD.md and docs/OBSERVABILITY.md —
        and no stray horaedb_batch_* family may exist outside the
        declared registry. (The batch_leader/batch_member/batch_cohort
        ledger fields ride the PR-2 lint automatically: column + family
        + docs mention.)"""
        import os
        import re

        from horaedb_tpu.utils.metrics import REGISTRY
        from horaedb_tpu.wlm import BATCH_METRIC_FAMILIES, COHORT_SIZE_BUCKETS

        here = os.path.dirname(__file__)
        docs = open(os.path.join(here, "..", "docs", "OBSERVABILITY.md")).read()
        wdocs = open(os.path.join(here, "..", "docs", "WORKLOAD.md")).read()
        families = set(REGISTRY.families())
        pat = re.compile(r"^horaedb_[a-z0-9_]+$")
        exposed = REGISTRY.expose()
        missing = []
        for fam in BATCH_METRIC_FAMILIES:
            if fam not in families:
                missing.append(f"{fam}: not registered")
            if not pat.match(fam) or not fam.endswith(self.SUFFIXES):
                missing.append(f"{fam}: violates naming lint")
            if f"`{fam}`" not in docs:
                missing.append(f"{fam}: undocumented in docs/OBSERVABILITY.md")
            if f"`{fam}`" not in wdocs:
                missing.append(f"{fam}: undocumented in docs/WORKLOAD.md")
        for kind in ("fused", "solo"):
            if f'kind="{kind}"' not in exposed:
                missing.append(f"label kind={kind}: not eagerly registered")
        for b in COHORT_SIZE_BUCKETS:
            if f'size="{b}"' not in exposed:
                missing.append(f"label size={b}: not eagerly registered")
        for fam in families:
            if fam.startswith("horaedb_batch_") and \
                    fam not in BATCH_METRIC_FAMILIES:
                missing.append(f"{fam}: live but undeclared in registry")
        # the [wlm.batch] knobs are operator surface: pinned to WORKLOAD.md
        for knob in ("enabled", "window", "max_cohort", "shapes"):
            if knob not in wdocs:
                missing.append(f"[wlm.batch] {knob}: undocumented")
        assert not missing, missing

    def test_device_families_declared_and_documented(self):
        """PR-15 lint extension (same contract as the agg-kernel/raw
        registries): every horaedb_device_* family declared in
        obs.device.DEVICE_METRIC_FAMILIES must be (a) registered live —
        with every DEVICE_KERNEL_KINDS label eagerly present on the
        dispatch/compile families and both compile outcomes — (b)
        convention-clean, (c) documented in docs/OBSERVABILITY.md — and
        no stray horaedb_device_* family may exist outside the declared
        registry. The device knobs are operator surface: pinned to
        docs/WORKLOAD.md. (The device_ms/device_dispatches/compile_hit
        ledger fields ride the PR-2 lint automatically: column + family
        + docs mention.)"""
        import os
        import re

        from horaedb_tpu.obs.device import (
            DEVICE_KERNEL_KINDS,
            DEVICE_METRIC_FAMILIES,
        )
        from horaedb_tpu.table_engine.system import DEVICE_NAME
        from horaedb_tpu.utils.metrics import REGISTRY

        here = os.path.dirname(__file__)
        docs = open(os.path.join(here, "..", "docs", "OBSERVABILITY.md")).read()
        wdocs = open(os.path.join(here, "..", "docs", "WORKLOAD.md")).read()
        families = set(REGISTRY.families())
        pat = re.compile(r"^horaedb_[a-z0-9_]+$")
        exposed = REGISTRY.expose()
        missing = []
        for fam in DEVICE_METRIC_FAMILIES:
            if fam not in families:
                missing.append(f"{fam}: not registered")
            if not pat.match(fam) or not fam.endswith(self.SUFFIXES):
                missing.append(f"{fam}: violates naming lint")
            if f"`{fam}`" not in docs:
                missing.append(f"{fam}: undocumented in docs/OBSERVABILITY.md")
        for kind in DEVICE_KERNEL_KINDS:
            if f'kernel="{kind}"' not in exposed:
                missing.append(f"label kernel={kind}: not eagerly registered")
        for outcome in ("compile", "hit"):
            if f'outcome="{outcome}"' not in exposed:
                missing.append(
                    f"label outcome={outcome}: not eagerly registered"
                )
        for fam in families:
            if fam.startswith("horaedb_device_") and \
                    fam not in DEVICE_METRIC_FAMILIES:
                missing.append(f"{fam}: live but undeclared in registry")
        # the system table + journal event kind are part of the contract
        if f"`{DEVICE_NAME}`" not in docs:
            missing.append(f"{DEVICE_NAME}: undocumented")
        from horaedb_tpu.utils.events import EVENT_KINDS

        if "kernel_compile" not in EVENT_KINDS:
            missing.append("kernel_compile: not in EVENT_KINDS")
        for knob in (
            "HORAEDB_DEVICE_TELEMETRY", "HORAEDB_DEVICE_COST_ANALYSIS",
        ):
            if f"`{knob}`" not in wdocs:
                missing.append(f"{knob}: undocumented in docs/WORKLOAD.md")
        # the sampling knobs went with the sampling: every dispatch is timed
        for knob in ("HORAEDB_DEVICE_" + "SAMPLE", "HORAEDB_DEVICE_" + "SLOW_MS"):
            if knob in wdocs or knob in docs:
                missing.append(f"{knob}: retired, still documented")
        assert not missing, missing

    def test_engine_families_live_after_flush(self, tmp_path):
        """Acceptance: /metrics exposes horaedb_flush_*, horaedb_compaction_*
        and horaedb_wal_* families after a flush+compaction cycle."""
        from horaedb_tpu.utils.metrics import REGISTRY

        db = horaedb_tpu.connect(str(tmp_path / "fams"))
        db.execute(
            "CREATE TABLE fam (h string TAG, v double, ts timestamp "
            "NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic "
            "WITH (segment_duration='1h')"
        )
        for i in range(db.instance.config.compaction_l0_trigger):
            db.execute(
                f"INSERT INTO fam (h, v, ts) VALUES ('a', {float(i)}, {100 + i})"
            )
            db.catalog.open("fam").flush()
        db.close()
        text = REGISTRY.expose()
        for family in (
            "horaedb_flush_duration_seconds",
            "horaedb_flush_bytes_total",
            "horaedb_compaction_requests_total",
            "horaedb_wal_append_duration_seconds",
            "horaedb_memtable_bytes",
        ):
            assert f"# TYPE {family}" in text, family
        assert REGISTRY.histogram("horaedb_wal_append_duration_seconds").count > 0


class TestDeadlineRegistryLint:
    """ISSUE-14 lint extension (same contract as the admission/raw
    registries): every family declared in
    utils/deadline.DEADLINE_METRIC_FAMILIES / CANCEL_METRIC_FAMILIES
    must be (a) registered live (stage/source labels eagerly present),
    (b) convention-clean, (c) documented in docs/OBSERVABILITY.md — and
    no stray horaedb_query_deadline_* / horaedb_query_cancel* family
    may exist outside the declared registries. The deadline knobs and
    the KILL surface are operator surface: pinned to docs/WORKLOAD.md.
    (The deadline_ms/timed_out/cancelled ledger fields ride the PR-2
    lint automatically: column + family + docs mention.)"""

    def test_deadline_families_declared_and_documented(self):
        import os
        import re

        from horaedb_tpu.utils.deadline import (
            CANCEL_METRIC_FAMILIES,
            CANCEL_SOURCES,
            DEADLINE_METRIC_FAMILIES,
            DEADLINE_STAGES,
        )
        from horaedb_tpu.utils.metrics import REGISTRY
        import horaedb_tpu.utils.querystats  # noqa: F401  (ledger families)

        here = os.path.dirname(__file__)
        docs = open(os.path.join(here, "..", "docs", "OBSERVABILITY.md")).read()
        wdocs = open(os.path.join(here, "..", "docs", "WORKLOAD.md")).read()
        families = set(REGISTRY.families())
        pat = re.compile(r"^horaedb_[a-z0-9_]+$")
        suffixes = TestMetricsNameLint.SUFFIXES
        exposed = REGISTRY.expose()
        missing = []
        declared = {**DEADLINE_METRIC_FAMILIES, **CANCEL_METRIC_FAMILIES}
        for fam in declared:
            if fam not in families:
                missing.append(f"{fam}: not registered")
            if not pat.match(fam) or not fam.endswith(suffixes):
                missing.append(f"{fam}: violates naming lint")
            if f"`{fam}`" not in docs:
                missing.append(f"{fam}: undocumented in docs/OBSERVABILITY.md")
        for stage in DEADLINE_STAGES:
            if f'stage="{stage}"' not in exposed:
                missing.append(f"label stage={stage}: not eagerly registered")
        for src in CANCEL_SOURCES:
            if f'source="{src}"' not in exposed:
                missing.append(f"label source={src}: not eagerly registered")
        for fam in families:
            if (
                fam.startswith("horaedb_query_deadline_")
                or fam.startswith("horaedb_query_cancel")
            ) and fam not in declared:
                missing.append(f"{fam}: live but undeclared in registry")
        # operator surface: the knobs, the header, the session knobs,
        # and the kill verbs are pinned to the workload doc
        for knob in (
            "query_timeout", "forward_timeout", "X-HoraeDB-Timeout-Ms",
            "max_execution_time", "statement_timeout", "KILL QUERY",
            "DELETE /debug/queries/{id}",
        ):
            if knob not in wdocs:
                missing.append(f"{knob}: undocumented in docs/WORKLOAD.md")
        # the system.public.queries schema is documented
        if "system.public.queries" not in docs:
            missing.append("system.public.queries: undocumented")
        assert not missing, missing

    def test_queries_table_registered_and_roundtrips(self):
        """system.public.queries serves the live registry: a registered
        entry appears as a row (with its budget) and vanishes on
        deregister."""
        from horaedb_tpu.table_engine.system import QueriesTable
        from horaedb_tpu.utils.deadline import QUERY_REGISTRY, Deadline

        d = Deadline(5000)
        entry = QUERY_REGISTRY.register(7, "SELECT lint", "tlint", d)
        try:
            rg = QueriesTable()._materialize()
            rows = {
                int(q): (s, t) for q, s, t in zip(
                    rg.columns["query_id"], rg.columns["sql"],
                    rg.columns["tenant"],
                )
            }
            assert entry.query_id in rows
            assert rows[entry.query_id] == ("SELECT lint", "tlint")
            got = rg.columns["deadline_ms"][
                list(rows).index(entry.query_id)
            ]
            assert int(got) == 5000
        finally:
            QUERY_REGISTRY.deregister(entry)
        rg = QueriesTable()._materialize()
        assert entry.query_id not in {int(q) for q in rg.columns["query_id"]}


class TestEventKindLint:
    """PR-5 lint extension (same contract as the family registries):
    every event kind declared in utils/events.EVENT_KINDS must (a) have
    an eagerly-registered ``horaedb_events_total{kind=...}`` counter,
    (b) round-trip through system.public.events, and (c) be documented
    in docs/OBSERVABILITY.md — and every kind string at a
    ``record_event("...")`` emit site anywhere in the source tree must
    be declared (an undeclared kind also fails loudly at runtime)."""

    def test_kinds_have_counters_rows_and_docs(self):
        import os

        from horaedb_tpu.table_engine.system import EventsTable
        from horaedb_tpu.utils.events import (
            EVENT_KINDS,
            EVENT_STORE,
            record_event,
        )
        from horaedb_tpu.utils.metrics import REGISTRY

        docs = open(
            os.path.join(os.path.dirname(__file__), "..", "docs",
                         "OBSERVABILITY.md")
        ).read()
        members = REGISTRY.families().get("horaedb_events_total", [])
        labeled = {m.labels.get("kind") for m in members}
        missing = []
        for kind in EVENT_KINDS:
            if kind not in labeled:
                missing.append(f"{kind}: no horaedb_events_total counter")
            if f"`{kind}`" not in docs:
                missing.append(f"{kind}: undocumented in OBSERVABILITY.md")
        # stray labeled counters (a kind removed from the registry but
        # still minting a series) fail too
        for kind in labeled - set(EVENT_KINDS):
            missing.append(f"{kind}: counter live but kind undeclared")
        assert "`horaedb_events_total`" in docs
        assert not missing, missing

        # every declared kind round-trips through the virtual table
        EVENT_STORE.clear()
        try:
            for kind in EVENT_KINDS:
                record_event(kind, table="lint")
            rg = EventsTable()._materialize()
            assert set(rg.columns["kind"]) == set(EVENT_KINDS)
            assert list(rg.columns["table_name"]) == ["lint"] * len(EVENT_KINDS)
        finally:
            EVENT_STORE.clear()

    def test_undeclared_kind_rejected(self):
        from horaedb_tpu.utils.events import record_event

        with pytest.raises(ValueError, match="undeclared event kind"):
            record_event("not_a_kind", table="x")

    def test_all_emit_sites_use_declared_kinds(self):
        """Source scan: every literal first argument to record_event()
        in the package must be a declared kind — a new emit site cannot
        mint a category no dashboard knows about."""
        import os
        import re

        from horaedb_tpu.utils.events import EVENT_KINDS

        pkg = os.path.join(os.path.dirname(__file__), "..", "horaedb_tpu")
        pat = re.compile(r"""record_event\(\s*["']([a-z_]+)["']""")
        undeclared = []
        for dirpath, _dirs, files in os.walk(pkg):
            for fn in files:
                if not fn.endswith(".py"):
                    continue
                src = open(os.path.join(dirpath, fn)).read()
                for kind in pat.findall(src):
                    if kind not in EVENT_KINDS:
                        undeclared.append(f"{fn}: {kind}")
        assert not undeclared, undeclared

    def test_self_monitoring_families_declared_and_documented(self):
        """The recorder's own families follow the same registry
        discipline: declared in SELF_MONITORING_METRIC_FAMILIES,
        registered live, convention-clean, documented — and no stray
        horaedb_self_* family exists outside the declared list. The
        [observability] knobs must be documented in WORKLOAD.md (the
        operator-knob index) as well as OBSERVABILITY.md."""
        import os
        import re

        from horaedb_tpu.engine.metrics_recorder import (
            SELF_MONITORING_METRIC_FAMILIES,
        )
        from horaedb_tpu.utils.metrics import REGISTRY

        here = os.path.dirname(__file__)
        docs = open(os.path.join(here, "..", "docs", "OBSERVABILITY.md")).read()
        wdocs = open(os.path.join(here, "..", "docs", "WORKLOAD.md")).read()
        families = set(REGISTRY.families())
        pat = re.compile(r"^horaedb_[a-z0-9_]+$")
        suffixes = TestMetricsNameLint.SUFFIXES
        missing = []
        for fam in SELF_MONITORING_METRIC_FAMILIES:
            if fam not in families:
                missing.append(f"{fam}: not registered")
            if not pat.match(fam) or not fam.endswith(suffixes):
                missing.append(f"{fam}: violates naming lint")
            if f"`{fam}`" not in docs:
                missing.append(f"{fam}: undocumented in OBSERVABILITY.md")
        for fam in families:
            if fam.startswith("horaedb_self_") and \
                    fam not in SELF_MONITORING_METRIC_FAMILIES:
                missing.append(f"{fam}: live but undeclared in registry")
        for knob in ("self_scrape", "self_scrape_interval",
                     "self_metrics_retention"):
            for name, text in (("OBSERVABILITY.md", docs),
                               ("WORKLOAD.md", wdocs)):
                if f"`{knob}`" not in text:
                    missing.append(f"{knob}: undocumented in {name}")
        assert not missing, missing


class TestRulesRegistryLint:
    """PR-8 lint extension (same contract as the self-monitoring
    registry): every family declared in rules/engine.RULES_METRIC_FAMILIES
    must be (a) registered live, (b) convention-clean, (c) documented in
    docs/OBSERVABILITY.md — with the per-kind eval labels eagerly
    registered — and no stray horaedb_rules_* / horaedb_alerts_* family
    may exist outside the declared registry. The [rules] knobs and the
    HORAEDB_ROLLUP kill switch are operator surface: pinned to
    docs/WORKLOAD.md; the `rollup` route is pinned to the ledger docs."""

    def test_rules_families_declared_and_documented(self):
        import os
        import re

        from horaedb_tpu.rules.engine import (
            RULE_EVAL_KINDS,
            RULES_METRIC_FAMILIES,
        )
        from horaedb_tpu.utils.metrics import REGISTRY

        here = os.path.dirname(__file__)
        docs = open(os.path.join(here, "..", "docs", "OBSERVABILITY.md")).read()
        wdocs = open(os.path.join(here, "..", "docs", "WORKLOAD.md")).read()
        families = set(REGISTRY.families())
        pat = re.compile(r"^horaedb_[a-z0-9_]+$")
        suffixes = TestMetricsNameLint.SUFFIXES
        exposed = REGISTRY.expose()
        missing = []
        for fam in RULES_METRIC_FAMILIES:
            if fam not in families:
                missing.append(f"{fam}: not registered")
            if not pat.match(fam) or not fam.endswith(suffixes):
                missing.append(f"{fam}: violates naming lint")
            if f"`{fam}`" not in docs:
                missing.append(f"{fam}: undocumented in OBSERVABILITY.md")
        for kind in RULE_EVAL_KINDS:
            if f'kind="{kind}"' not in exposed:
                missing.append(f"label kind={kind}: not eagerly registered")
        for fam in families:
            if (
                fam.startswith("horaedb_rules_")
                or fam.startswith("horaedb_alerts_")
            ) and fam not in RULES_METRIC_FAMILIES:
                missing.append(f"{fam}: live but undeclared in registry")
        for knob in (
            "enabled", "eval_interval", "grace", "recording", "alerts",
            "rollup_tables", "rollup_raw_ttl", "rollup_1m_ttl",
            "rollup_1h_ttl", "recording_ttl",
        ):
            if f"`{knob}`" not in wdocs:
                missing.append(f"{knob}: undocumented in docs/WORKLOAD.md")
        if "`HORAEDB_ROLLUP" not in wdocs:
            missing.append("HORAEDB_ROLLUP: undocumented in docs/WORKLOAD.md")
        # the rewrite's route is part of the documented ledger surface
        if "`rollup`" not in docs:
            missing.append("route=rollup: undocumented in OBSERVABILITY.md")
        assert not missing, missing

    def test_alerts_table_registered_in_system_catalog(self):
        from horaedb_tpu.table_engine.system import (
            ALERTS_NAME,
            AlertsTable,
            open_system_table,
        )

        t = open_system_table(None, ALERTS_NAME)
        assert isinstance(t, AlertsTable)
        cols = {c.name for c in t.schema.columns}
        assert {"rule", "labels", "state", "value", "active_since",
                "fired_at", "resolved_at"} <= cols


class TestReplicaRegistryLint:
    """PR-10 lint extension (same contract as the rules registry) for the
    replicated-follower-read families — see the method docstring."""

    def test_replica_families_declared_and_documented(self):
        """PR-10 lint extension (same contract as the rules registry):
        every family declared in cluster/replica.REPLICA_METRIC_FAMILIES
        must be (a) registered live, (b) convention-clean, (c) documented
        in docs/OBSERVABILITY.md — with the per-outcome read labels
        eagerly registered — and no stray horaedb_replica_* family may
        exist outside the declared registry. The [cluster] replica knobs
        are operator surface: pinned to docs/WORKLOAD.md; the `follower`
        route and `replica_lag_ms` ledger field are pinned to the ledger
        docs."""
        import os
        import re

        from horaedb_tpu.cluster.replica import (
            REPLICA_METRIC_FAMILIES,
            REPLICA_READ_OUTCOMES,
        )
        from horaedb_tpu.utils.metrics import REGISTRY

        here = os.path.dirname(__file__)
        docs = open(os.path.join(here, "..", "docs", "OBSERVABILITY.md")).read()
        wdocs = open(os.path.join(here, "..", "docs", "WORKLOAD.md")).read()
        families = set(REGISTRY.families())
        pat = re.compile(r"^horaedb_[a-z0-9_]+$")
        suffixes = TestMetricsNameLint.SUFFIXES
        exposed = REGISTRY.expose()
        missing = []
        for fam in REPLICA_METRIC_FAMILIES:
            if fam not in families:
                missing.append(f"{fam}: not registered")
            if not pat.match(fam) or not fam.endswith(suffixes):
                missing.append(f"{fam}: violates naming lint")
            if f"`{fam}`" not in docs:
                missing.append(f"{fam}: undocumented in OBSERVABILITY.md")
        for outcome in REPLICA_READ_OUTCOMES:
            if f'outcome="{outcome}"' not in exposed:
                missing.append(
                    f"label outcome={outcome}: not eagerly registered"
                )
        for fam in families:
            if fam.startswith("horaedb_replica_") and \
                    fam not in REPLICA_METRIC_FAMILIES:
                missing.append(f"{fam}: live but undeclared in registry")
        for knob in ("read_replicas", "read_staleness"):
            if f"`{knob}`" not in wdocs:
                missing.append(f"{knob}: undocumented in docs/WORKLOAD.md")
        # the follower serving path is part of the documented ledger
        # surface: the route value and the staleness headers
        if "`follower`" not in docs:
            missing.append("route=follower: undocumented in OBSERVABILITY.md")
        if "X-HoraeDB-Read-Staleness" not in wdocs:
            missing.append(
                "X-HoraeDB-Read-Staleness: undocumented in docs/WORKLOAD.md"
            )
        assert not missing, missing


class TestSloRegistryLint:
    """PR-11 lint extension (same contract as the rules/replica
    registries) for the SLO plane: every family declared in
    slo/evaluator.SLO_METRIC_FAMILIES must be (a) registered live — the
    per-objective burn-rate/breach series eagerly at evaluator load, with
    both window labels — (b) convention-clean, (c) documented in
    docs/OBSERVABILITY.md; no stray horaedb_slo_* family may exist
    outside the declared registry. The per-class query-latency family
    (proxy.QUERY_CLASS_METRIC_FAMILIES, the canonical SLO indicator) is
    held to the same contract with every admission-class label live. The
    [slo] knobs and the [observability] event_ring knob are operator
    surface: pinned to docs/WORKLOAD.md. The event-journal drop counter
    must be registered + documented (the "no seq gaps" invariant is only
    falsifiable with drops accounted)."""

    def test_slo_families_declared_and_documented(self):
        import os
        import re

        import horaedb_tpu
        from horaedb_tpu.slo import BURN_WINDOWS, SLO_METRIC_FAMILIES, SloEvaluator
        from horaedb_tpu.utils.config import SloSection
        from horaedb_tpu.utils.metrics import REGISTRY

        db = horaedb_tpu.connect(None)
        try:
            # one loaded objective so the labeled series exist
            ev = SloEvaluator(
                db,
                SloSection(objectives=["slo_lint_probe := 0 <= 1"]),
            )
            assert len(ev) == 1
            here = os.path.dirname(__file__)
            docs = open(
                os.path.join(here, "..", "docs", "OBSERVABILITY.md")
            ).read()
            wdocs = open(
                os.path.join(here, "..", "docs", "WORKLOAD.md")
            ).read()
            families = set(REGISTRY.families())
            pat = re.compile(r"^horaedb_[a-z0-9_]+$")
            suffixes = TestMetricsNameLint.SUFFIXES
            exposed = REGISTRY.expose()
            missing = []
            for fam in SLO_METRIC_FAMILIES:
                if fam not in families:
                    missing.append(f"{fam}: not registered")
                if not pat.match(fam) or not fam.endswith(suffixes):
                    missing.append(f"{fam}: violates naming lint")
                if f"`{fam}`" not in docs:
                    missing.append(f"{fam}: undocumented in OBSERVABILITY.md")
            for window in BURN_WINDOWS:
                if f'window="{window}"' not in exposed:
                    missing.append(
                        f"label window={window}: not eagerly registered"
                    )
            for fam in families:
                if fam.startswith("horaedb_slo_") and \
                        fam not in SLO_METRIC_FAMILIES:
                    missing.append(f"{fam}: live but undeclared in registry")
            for knob in ("objectives", "fast_window", "slow_window",
                         "burn_threshold", "event_ring"):
                if f"`{knob}`" not in wdocs:
                    missing.append(f"{knob}: undocumented in docs/WORKLOAD.md")
            assert not missing, missing
        finally:
            db.close()

    def test_query_class_family_declared_and_documented(self):
        import os
        import re

        from horaedb_tpu.proxy import (
            ADMISSION_CLASSES,
            QUERY_CLASS_METRIC_FAMILIES,
        )
        from horaedb_tpu.utils.metrics import REGISTRY

        here = os.path.dirname(__file__)
        docs = open(os.path.join(here, "..", "docs", "OBSERVABILITY.md")).read()
        families = set(REGISTRY.families())
        pat = re.compile(r"^horaedb_[a-z0-9_]+$")
        suffixes = TestMetricsNameLint.SUFFIXES
        exposed = REGISTRY.expose()
        missing = []
        for fam in QUERY_CLASS_METRIC_FAMILIES:
            if fam not in families:
                missing.append(f"{fam}: not registered")
            if not pat.match(fam) or not fam.endswith(suffixes):
                missing.append(f"{fam}: violates naming lint")
            if f"`{fam}`" not in docs:
                missing.append(f"{fam}: undocumented in OBSERVABILITY.md")
        for cls in ADMISSION_CLASSES:
            if f'class="{cls}"' not in exposed:
                missing.append(f"label class={cls}: not eagerly registered")
        for fam in families:
            if fam.startswith("horaedb_query_class_") and \
                    fam not in QUERY_CLASS_METRIC_FAMILIES:
                missing.append(f"{fam}: live but undeclared in registry")
        assert not missing, missing

    def test_event_drop_counter_registered_and_documented(self):
        import os

        from horaedb_tpu.utils.metrics import REGISTRY

        here = os.path.dirname(__file__)
        docs = open(os.path.join(here, "..", "docs", "OBSERVABILITY.md")).read()
        assert "horaedb_events_dropped_total" in REGISTRY.families()
        assert "`horaedb_events_dropped_total`" in docs

    def test_slo_table_registered_in_system_catalog(self):
        import horaedb_tpu
        from horaedb_tpu.slo import SloEvaluator
        from horaedb_tpu.table_engine.system import (
            SLO_NAME,
            SloTable,
            open_system_table,
        )
        from horaedb_tpu.utils.config import SloSection

        t = open_system_table(None, SLO_NAME)
        assert isinstance(t, SloTable)
        cols = {c.name for c in t.schema.columns}
        assert {"objective", "state", "value", "bound", "target",
                "burn_fast", "burn_slow", "breaches", "since"} <= cols
        db = horaedb_tpu.connect(None)
        try:
            ev = SloEvaluator(
                db, SloSection(objectives=["slo_lint_table := 0 <= 1"])
            )
            ev.evaluate_round()
            rg = t._materialize()
            assert "slo_lint_table" in list(rg.columns["objective"])
        finally:
            db.close()


class TestDecisionRegistryLint:
    """ISSUE-16 lint extension (same contract as the slo/elastic/replica
    registries) for the decision plane: every family declared in
    obs/decisions.DECISION_METRIC_FAMILIES and
    CALIBRATION_METRIC_FAMILIES must be (a) registered live — the
    per-loop series eagerly at module import for every declared loop,
    the calibration error gauge with every window/kind label — (b)
    convention-clean, (c) documented in docs/OBSERVABILITY.md; no stray
    horaedb_decision_*/horaedb_calibration_* family may exist outside
    the declared registries. The [observability] decision_ring knob and
    the plane's env switches are operator surface: pinned to
    docs/WORKLOAD.md. The decision event kinds must be declared in
    EVENT_KINDS (counters + docs ride the event-kind lint)."""

    def test_decision_families_declared_and_documented(self):
        import os
        import re

        from horaedb_tpu.obs.decisions import (
            CALIBRATION_ERROR_KINDS,
            CALIBRATION_METRIC_FAMILIES,
            CALIBRATION_WINDOWS,
            DECISION_LOOPS,
            DECISION_METRIC_FAMILIES,
        )
        from horaedb_tpu.utils.events import EVENT_KINDS
        from horaedb_tpu.utils.metrics import REGISTRY

        here = os.path.dirname(__file__)
        docs = open(os.path.join(here, "..", "docs", "OBSERVABILITY.md")).read()
        wdocs = open(os.path.join(here, "..", "docs", "WORKLOAD.md")).read()
        families = set(REGISTRY.families())
        pat = re.compile(r"^horaedb_[a-z0-9_]+$")
        suffixes = TestMetricsNameLint.SUFFIXES
        exposed = REGISTRY.expose()
        missing = []
        for fam in DECISION_METRIC_FAMILIES + CALIBRATION_METRIC_FAMILIES:
            if fam not in families:
                missing.append(f"{fam}: not registered")
            if not pat.match(fam) or not fam.endswith(suffixes):
                missing.append(f"{fam}: violates naming lint")
            if f"`{fam}`" not in docs:
                missing.append(f"{fam}: undocumented in OBSERVABILITY.md")
        for loop in DECISION_LOOPS:
            if f'loop="{loop}"' not in exposed:
                missing.append(f"label loop={loop}: not eagerly registered")
        for window in CALIBRATION_WINDOWS:
            if f'window="{window}"' not in exposed:
                missing.append(
                    f"label window={window}: not eagerly registered"
                )
        for kind in CALIBRATION_ERROR_KINDS:
            if f'kind="{kind}"' not in exposed:
                missing.append(f"label kind={kind}: not eagerly registered")
        for fam in families:
            if (fam.startswith("horaedb_decision_")
                    and fam not in DECISION_METRIC_FAMILIES) or \
                    (fam.startswith("horaedb_calibration_")
                     and fam not in CALIBRATION_METRIC_FAMILIES):
                missing.append(f"{fam}: live but undeclared in registry")
        for knob in ("decision_ring", "HORAEDB_DECISIONS",
                     "HORAEDB_DECISION_EXPIRE_MS",
                     "HORAEDB_CALIBRATION_FAST_S"):
            if f"`{knob}`" not in wdocs:
                missing.append(f"{knob}: undocumented in docs/WORKLOAD.md")
        for kind in ("decision_resolved", "loop_miscalibrated"):
            if kind not in EVENT_KINDS:
                missing.append(f"event kind {kind}: undeclared in EVENT_KINDS")
        assert not missing, missing

    def test_decision_tables_registered_in_system_catalog(self):
        from horaedb_tpu.obs.decisions import DECISION_JOURNAL
        from horaedb_tpu.table_engine.system import (
            CALIBRATION_NAME,
            DECISIONS_NAME,
            open_system_table,
        )

        t = open_system_table(None, DECISIONS_NAME)
        cols = {c.name for c in t.schema.columns}
        assert {"id", "loop", "decision_key", "choice", "features",
                "predicted", "resolved", "actual", "outcome",
                "error", "trace_id"} <= cols
        c = open_system_table(None, CALIBRATION_NAME)
        ccols = {cc.name for cc in c.schema.columns}
        assert {"loop", "samples", "ewma_signed", "ewma_abs",
                "fast_abs", "slow_abs", "miscalibrated", "issued",
                "resolved", "expired", "missed", "unresolved"} <= ccols
        # one row per declared loop, always — the ledger is never absent
        rg = c._materialize()
        from horaedb_tpu.obs.decisions import DECISION_LOOPS
        assert set(rg.columns["loop"]) == set(DECISION_LOOPS)
        assert DECISION_JOURNAL.stats()["capacity"] > 0


class TestElasticRegistryLint:
    """PR-12 lint extension (same contract as the slo/replica/rules
    registries) for the elastic control loop: every family declared in
    meta/elastic.ELASTIC_METRIC_FAMILIES must be (a) registered live —
    the per-action counter series eagerly at module import — (b)
    convention-clean, (c) documented in docs/OBSERVABILITY.md; no stray
    horaedb_elastic_* family may exist outside the declared registry.
    The [cluster.elastic] knobs are operator surface: pinned to
    docs/WORKLOAD.md. The elastic event kinds must be declared in
    EVENT_KINDS (counters + docs ride the event-kind lint)."""

    def test_elastic_families_declared_and_documented(self):
        import os
        import re

        from horaedb_tpu.meta.elastic import (
            ELASTIC_ACTIONS,
            ELASTIC_METRIC_FAMILIES,
        )
        from horaedb_tpu.utils.metrics import REGISTRY

        here = os.path.dirname(__file__)
        docs = open(os.path.join(here, "..", "docs", "OBSERVABILITY.md")).read()
        wdocs = open(os.path.join(here, "..", "docs", "WORKLOAD.md")).read()
        families = set(REGISTRY.families())
        pat = re.compile(r"^horaedb_[a-z0-9_]+$")
        suffixes = TestMetricsNameLint.SUFFIXES
        exposed = REGISTRY.expose()
        missing = []
        for fam in ELASTIC_METRIC_FAMILIES:
            if fam not in families:
                missing.append(f"{fam}: not registered")
            if not pat.match(fam) or not fam.endswith(suffixes):
                missing.append(f"{fam}: violates naming lint")
            if f"`{fam}`" not in docs:
                missing.append(f"{fam}: undocumented in OBSERVABILITY.md")
        for action in ELASTIC_ACTIONS:
            if f'action="{action}"' not in exposed:
                missing.append(f"label action={action}: not eagerly registered")
        for fam in families:
            if fam.startswith("horaedb_elastic_") and \
                    fam not in ELASTIC_METRIC_FAMILIES:
                missing.append(f"{fam}: live but undeclared in registry")
        for knob in ("dry_run", "min_replicas", "max_replicas",
                     "scale_up_qps", "scale_down_qps", "fast_window",
                     "slow_window", "decide_interval", "cooldown",
                     "move_cooldown", "action_budget", "quarantine_after",
                     "node_stable", "min_move_qps", "prewarm",
                     "prewarm_timeout"):
            if f"`{knob}`" not in wdocs:
                missing.append(f"{knob}: undocumented in docs/WORKLOAD.md")
        assert not missing, missing

    def test_elastic_event_kinds_declared(self):
        from horaedb_tpu.utils.events import EVENT_KINDS

        assert {"elastic_decision", "elastic_action",
                "elastic_quarantined", "elastic_released"} <= set(EVENT_KINDS)

    def test_table_name_column_in_query_stats(self):
        """The elastic load signal: the proxy stamps the statement's
        primary table into the ledger, and query_stats serves it."""
        import horaedb_tpu
        from horaedb_tpu.table_engine.system import QueryStatsTable

        cols = {c.name for c in QueryStatsTable().schema.columns}
        assert "table_name" in cols
        db = horaedb_tpu.connect(None)
        try:
            db.execute(
                "CREATE TABLE lint_tn (v double, ts timestamp NOT NULL, "
                "TIMESTAMP KEY(ts)) ENGINE=Analytic"
            )
            from horaedb_tpu.proxy import Proxy

            p = Proxy(db)
            try:
                p.handle_sql("SELECT count(v) AS c FROM lint_tn")
            finally:
                p.close()
            from horaedb_tpu.utils.querystats import STATS_STORE

            rows = [
                e for e in STATS_STORE.list()
                if e.get("table_name") == "lint_tn"
            ]
            assert rows, "no query_stats row carried table_name"
        finally:
            db.close()


class TestLivewindowRegistryLint:
    """ISSUE-18 lint extension (same contract as the decision/elastic
    registries) for the live window state plane: every family declared
    in state/livewindow.LIVEWINDOW_METRIC_FAMILIES must be (a)
    registered live — eagerly at module import, so a node that never
    promotes still exposes the plane as flat zeros — (b)
    convention-clean, (c) documented in docs/OBSERVABILITY.md; no stray
    horaedb_livewindow_* family may exist outside the declared
    registry. The plane's env switches are operator surface: pinned to
    docs/WORKLOAD.md."""

    def test_livewindow_families_declared_and_documented(self):
        import os
        import re

        from horaedb_tpu.state.livewindow import LIVEWINDOW_METRIC_FAMILIES
        from horaedb_tpu.utils.metrics import REGISTRY

        here = os.path.dirname(__file__)
        docs = open(os.path.join(here, "..", "docs", "OBSERVABILITY.md")).read()
        wdocs = open(os.path.join(here, "..", "docs", "WORKLOAD.md")).read()
        families = set(REGISTRY.families())
        pat = re.compile(r"^horaedb_[a-z0-9_]+$")
        suffixes = TestMetricsNameLint.SUFFIXES
        missing = []
        for fam in LIVEWINDOW_METRIC_FAMILIES:
            if fam not in families:
                missing.append(f"{fam}: not registered")
            if not pat.match(fam) or not fam.endswith(suffixes):
                missing.append(f"{fam}: violates naming lint")
            if f"`{fam}`" not in docs:
                missing.append(f"{fam}: undocumented in OBSERVABILITY.md")
        for fam in families:
            if (fam.startswith("horaedb_livewindow_")
                    and fam not in LIVEWINDOW_METRIC_FAMILIES):
                missing.append(f"{fam}: live but undeclared in registry")
        for knob in ("HORAEDB_LIVEWINDOW", "HORAEDB_LIVEWINDOW_BUDGET",
                     "HORAEDB_LIVEWINDOW_DEPTH", "HORAEDB_LIVEWINDOW_PROMOTE",
                     "HORAEDB_LIVEWINDOW_MAX_GROUPS"):
            if f"`{knob}`" not in wdocs:
                missing.append(f"{knob}: undocumented in docs/WORKLOAD.md")
        assert not missing, missing

    def test_livewindow_loop_declared_in_decision_plane(self):
        from horaedb_tpu.obs.decisions import (
            _EVENT_SAMPLE,
            DECISION_LOOPS,
        )

        assert "livewindow" in DECISION_LOOPS
        assert "livewindow" in _EVENT_SAMPLE


class TestLayoutRegistryLint:
    """ISSUE-19 lint extension for the compressed-layout plane: the
    layout knobs are operator surface (pinned to docs/WORKLOAD.md), the
    layout_tuner loop is a first-class decision-plane citizen, and the
    occupancy table's encoding/logical_rows columns exist in the
    system-catalog schema AND in docs/OBSERVABILITY.md with the full
    encoding vocabulary spelled out."""

    KNOBS = (
        "HORAEDB_CACHE_LAYOUT",
        "HORAEDB_CACHE_DICT_MAX",
        "HORAEDB_CACHE_DELTA_MAX_BITS",
    )
    ENCODINGS = ("raw", "bf16", "dict8", "dict16", "delta")

    def test_layout_knobs_documented(self):
        import os

        here = os.path.dirname(__file__)
        wdocs = open(os.path.join(here, "..", "docs", "WORKLOAD.md")).read()
        missing = [
            k for k in self.KNOBS if f"`{k}`" not in wdocs
        ]
        assert not missing, f"undocumented in docs/WORKLOAD.md: {missing}"

    def test_layout_loop_declared_in_decision_plane(self):
        from horaedb_tpu.obs.decisions import (
            _EVENT_SAMPLE,
            DECISION_LOOPS,
        )

        assert "layout_tuner" in DECISION_LOOPS
        assert "layout_tuner" in _EVENT_SAMPLE
        # the former standalone loop is GONE — promotions resolve
        # through layout_tuner now
        assert "dtype_tuner" not in DECISION_LOOPS

    def test_device_table_carries_encoding_columns(self):
        import os

        from horaedb_tpu.table_engine.system import (
            DEVICE_NAME,
            open_system_table,
        )

        t = open_system_table(None, DEVICE_NAME)
        cols = {c.name for c in t.schema.columns}
        assert {"encoding", "logical_rows"} <= cols
        here = os.path.dirname(__file__)
        docs = open(
            os.path.join(here, "..", "docs", "OBSERVABILITY.md")
        ).read()
        assert "`encoding`" in docs and "`logical_rows`" in docs
        for enc in self.ENCODINGS:
            assert f"`{enc}`" in docs, f"encoding {enc} undocumented"
