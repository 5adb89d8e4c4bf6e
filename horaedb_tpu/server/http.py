"""HTTP front end (ref: src/server/src/http.rs routes :214-713).

Routes (default port 5440, matching the reference's http default,
config.rs:176):

    POST /sql            {"query": "..."}            -> {"rows": [...],
                         "names": [...]}, encoded from the result's columns
                         (query/result_json), or {"affected_rows": N} for
                         writes/DDL
    POST /write          {"table": t, "rows": [{...}]} JSON bulk write
    GET  /metrics        Prometheus text
    GET  /route/{table}  routing info (standalone: self)
    GET  /debug/config   engine + server config dump
    GET  /debug/status   node status document (uptime, shards, replay,
                         scheduler queues, memtables, admission slots)
    GET  /debug/events   engine event journal (?kind=, ?limit=)
    GET  /debug/tables   per-table metrics (memtable/sst bytes, seqs)
    GET  /debug/hotspot  hottest tables by reads/writes
    GET  /debug/workload live admission/dedup/quota state (wlm)
    GET  /debug/device   device telemetry plane (HBM residency, compile stats)
    GET  /debug/livewindow  live window ring states (+ DELETE .../{key} evicts)
    GET  /debug/alerts   rule-engine alert state (pending/firing/resolved)
    PUT  /debug/slow_threshold/{seconds}  live slow-log threshold
    POST /admin/block    {"tables": [...]} / DELETE to unblock
    GET/POST/DELETE /admin/quota  per-tenant/table token buckets
    GET/POST/DELETE /admin/rules  recording/alert rules (rules engine)
    GET  /health         liveness (?ready=1 -> readiness gate, 503 until
                         WAL replay done / a shard opened / rule state
                         loaded)
"""

from __future__ import annotations

import asyncio
import functools
import json
import logging
from typing import Optional

from aiohttp import web

from ..db import Connection, connect
from ..proxy import BlockedError, OverloadedError, Proxy, QuotaExceededError
from ..query.executor import ResultSet
from ..query.interpreters import AffectedRows
from ..query.result_json import SqlAnswer, dumps as _dumps
from ..utils.metrics import REGISTRY

logger = logging.getLogger("horaedb_tpu.server")


def _query_flag(request, name: str) -> bool:
    """Boolean query parameter: ``?x=1``/``true``/bare presence enable,
    but an explicit ``?x=0``/``false``/``no`` does NOT — plain string
    truthiness would treat ``?x=0`` as on."""
    val = request.query.get(name)
    if val is None:
        return False
    return val.strip().lower() not in ("0", "false", "no")

DEFAULT_HTTP_PORT = 5440  # ref: config.rs:176


async def _client_session(app: web.Application):
    """One pooled forwarding session per app (keep-alive to peers).

    Lazily created (must be born inside the running event loop); the
    cleanup hook is registered at create_app time — aiohttp freezes the
    signal lists once the app starts.
    """
    import aiohttp

    session = app.get("forward_session")
    if session is None or session.closed:
        session = aiohttp.ClientSession()
        app["forward_session"] = session
    return session


async def _close_client_session(app: web.Application):
    s = app.get("forward_session")
    if s is not None and not s.closed:
        await s.close()


def _table_of_statement(stmt) -> Optional[str]:
    """The table a statement targets, for routing (None = node-local)."""
    from ..query import ast

    if isinstance(stmt, ast.Explain):
        stmt = stmt.inner
    return getattr(stmt, "table", None)


def _answer_of(out, wire: str):
    """A statement's result as the gateway hands it on, called on the worker
    thread that ran the statement. Rows become a ``SqlAnswer``; where the
    wire is HTTP the per-value part of its body is made here, under span
    ``rows``, so the event loop never stalls for the length of an encode and
    the handler's ``encode`` span is left the body's assembly."""
    from ..utils.tracectx import span

    if isinstance(out, AffectedRows):
        return out
    answer = SqlAnswer(out.names, out.columns, out.nulls)
    if wire == "http":
        with span("rows") as sp:
            answer.prepare()
            sp.set(rows=answer.num_rows, route=answer.route)
    return answer


class Served(tuple):
    """``(kind, payload)`` as ``SqlGateway.execute`` hands it to a wire
    handler, plus the id of the ``sql`` trace that produced it: the key
    of ``/debug/trace/{id}`` and ``query_stats`` (None where no statement
    ran on this node under a handler's trace)."""

    request_id = None


FORWARD_HEADER = "X-HoraeDB-Forwarded"
# The id of the statement's trace, on every /sql response a statement of
# this node produced (a coalesced twin carries its leader's).
REQUEST_ID_HEADER = "X-HoraeDB-Request-Id"
# Deadline propagation (utils/deadline): the client's per-request time
# budget in milliseconds. Forwarding hops re-stamp it with the
# REMAINING budget, so a multi-hop read decrements one budget instead
# of burning a fresh fixed timeout per hop; a hop that receives <= 0
# refuses the work on arrival (504).
TIMEOUT_HEADER = "X-HoraeDB-Timeout-Ms"
# Replicated follower reads (cluster/replica): a forwarded read marked
# with REPLICA_READ_HEADER asks the receiving node to serve from its
# read-only follower handle; REPLICA_EPOCH_HEADER carries the shard
# epoch the forwarder observed (a follower trailing it refuses);
# STALENESS_HEADER is the per-request bounded-staleness opt-in.
REPLICA_READ_HEADER = "X-HoraeDB-Replica-Read"
REPLICA_EPOCH_HEADER = "X-HoraeDB-Replica-Epoch"
STALENESS_HEADER = "X-HoraeDB-Read-Staleness"


@functools.lru_cache(maxsize=None)
def latency_histogram(protocol: str):
    """Per-protocol labelset of the ONE front-end latency family —
    every listener (http/mysql/postgres) passes its protocol to
    ``SqlGateway.execute`` instead of keeping its own timing wrapper."""
    return REGISTRY.histogram(
        "horaedb_request_duration_seconds",
        "front-end request latency by protocol",
        labels={"protocol": protocol},
    )


def _follower_reads_enabled() -> bool:
    """HORAEDB_FOLLOWER_READS=0 pins every read to the leader (kill
    switch for the replicated follower serving path)."""
    import os

    return os.environ.get("HORAEDB_FOLLOWER_READS", "1") != "0"


def _replica_select(stmt):
    """The SELECT a follower replica may serve (plain SELECT, or EXPLAIN
    over one), else None. Writes/DDL never touch replicas; joins, CTEs
    and unions keep their existing leader-side handling."""
    from ..query import ast as _ast

    inner = stmt.inner if isinstance(stmt, _ast.Explain) else stmt
    return inner if isinstance(inner, _ast.Select) else None


def _parse_timeout_ms(raw: Optional[str]) -> Optional[float]:
    """X-HoraeDB-Timeout-Ms header -> milliseconds (None = absent;
    invalid values read as absent rather than failing the query)."""
    if not raw:
        return None
    try:
        v = float(raw.strip())
    except ValueError:
        return None
    return v if v == v else None  # NaN reads as absent


def _forward_client_timeout(app, deadline=None):
    """Per-call timeout for a forwarding hop: min([limits]
    forward_timeout, the request's remaining budget) — replaces the old
    fixed ClientTimeout(total=30) constants."""
    import aiohttp

    cap = app.get("forward_timeout_s") or 30.0
    total = cap if deadline is None else deadline.cap_timeout(cap)
    return aiohttp.ClientTimeout(total=total)


def _budget_headers(deadline) -> dict:
    """The remaining-budget header a forwarded hop carries (empty when
    the request is unbounded)."""
    if deadline is None:
        return {}
    rem = deadline.remaining_ms()
    if rem is None:
        return {}
    return {TIMEOUT_HEADER: str(max(1, rem))}


def _parse_staleness(raw: Optional[str]) -> Optional[int]:
    """X-HoraeDB-Read-Staleness header -> milliseconds (None = absent,
    invalid values read as absent rather than failing the query)."""
    if not raw:
        return None
    from ..engine.options import parse_duration_ms

    try:
        s = raw.strip()
        return parse_duration_ms(s) if not s.isdigit() else int(s) * 1000
    except Exception:
        return None


def _write_fence(cluster, router, table: str) -> Optional[tuple[int, str]]:
    """Single-writer discipline for the write paths (cluster mode).

    None = safe to proceed (execute locally or forward); a (status, msg)
    pair = the write must be refused NOW. The catalog registry lives in
    shared storage, so "the table opens locally" proves nothing about
    ownership — only the shard set + a live lease (or an authoritative
    remote route) makes a write safe.
    """
    if cluster is None:
        return None
    if cluster.owns_table(table):
        from ..cluster import ShardError

        try:
            cluster.ensure_table_writable(table)
        except ShardError as e:
            return 503, str(e)
        return None
    r = router.route(table)
    if not r.is_local:
        return None  # forwarded to the owner below
    if r.source == "fallback":
        return 503, f"coordinator unreachable; cannot safely accept writes for {table!r}"
    if r.source == "meta":
        # Coordinator says this node owns it, but the shard isn't open
        # here yet (transfer in flight) — retryable, never unfenced.
        return 503, f"shard for {table!r} is opening on this node; retry"
    return None  # meta-unknown: local execution yields table-not-found


class SqlGateway:
    """THE routed SQL pipeline — every protocol front end (HTTP /sql,
    MySQL wire, PostgreSQL wire) funnels through this one path so cluster
    routing, DDL-via-coordinator, write fencing, and the proxy's
    limiter/metrics/slow-log apply to ALL protocols, not just HTTP
    (ref: every listener shares one Proxy in the reference, lib.rs:110).

    ``execute`` returns one of:
        ("affected", n)
        ("rows", answer)    a ``query/result_json.SqlAnswer``: ``.names``
                            and either face, made once each — ``.body()``,
                            the /sql answer's bytes (encoded from the
                            result's columns on the worker thread that ran
                            the statement when the wire is HTTP), and
                            ``.rows()``, dict rows (MySQL, PostgreSQL);
                            ``names, rows = answer`` still unpacks
        ("error", (http_status, message, extra))

    ``extra`` classifies shed/blocked/quota errors for protocol-correct
    wire mapping: {"kind": "blocked"|"overloaded"|"quota",
    "retry_after_s": float} — HTTP turns retry_after_s into a
    Retry-After header; MySQL and PG map kind to their native error
    code / SQLSTATE instead of a generic internal error.
    """

    def __init__(self, app: web.Application) -> None:
        self.app = app
        # single-flight dedup of identical in-flight reads (ref:
        # proxy/src/read.rs:89,167 + components/notifier RequestNotifiers —
        # concurrent identical SELECTs share one execution; followers get
        # the leader's result instead of re-running the scan). The key
        # includes a write epoch so a SELECT issued after this node
        # accepted a write never joins a pre-write execution — same-node
        # read-your-writes survives the dedup.
        self._inflight: dict[tuple[int, str], asyncio.Future] = {}
        self._write_epoch = 0
        self._m_deduped = REGISTRY.counter(
            "horaedb_read_dedup_total", "reads served from an in-flight twin"
        )

    async def execute(
        self,
        query: str,
        already_forwarded: bool = False,
        protocol: str | None = None,
        tenant: str = "default",
        replica_read: bool = False,
        staleness_ms: Optional[int] = None,
        replica_epoch: Optional[int] = None,
        timeout_ms: Optional[float] = None,
        wire: str = "http",
    ):
        if protocol is not None:
            import time as _time

            t0 = _time.perf_counter()
            try:
                return await self.execute(
                    query, already_forwarded, tenant=tenant,
                    replica_read=replica_read, staleness_ms=staleness_ms,
                    replica_epoch=replica_epoch, timeout_ms=timeout_ms,
                    wire=protocol,
                )
            finally:
                latency_histogram(protocol).observe(_time.perf_counter() - t0)
        app = self.app
        # The time budget starts HERE, at wire ingress: the client's
        # X-HoraeDB-Timeout-Ms / session knob, else the [limits]
        # query_timeout default. Already-expired work (a forwarded hop
        # whose budget drained in flight) is refused before parsing.
        from ..utils.deadline import Deadline

        if timeout_ms is not None and timeout_ms <= 0:
            # an explicit zero/negative budget IS "already expired":
            # refuse the work on arrival instead of starting it
            from ..utils.deadline import note_expired

            note_expired("ingress")
            return "error", (
                504,
                "request arrived with an exhausted time budget",
                {"kind": "deadline", "retry_after_s": 1.0},
            )
        deadline = Deadline(
            timeout_ms if timeout_ms is not None
            else app.get("query_timeout_ms", 60_000.0),
            proto=wire,
        )
        conn: Connection = app["conn"]
        proxy: Proxy = app["proxy"]
        router = app["router"]
        cluster = app["cluster"]
        loop = asyncio.get_running_loop()
        if router is not None:
            # Routing needs the target table before execution. The parse
            # here is routing-only; standalone mode skips it entirely.
            try:
                stmt = conn.frontend.parse_sql(query)
            except Exception as e:
                proxy._m_queries.inc()
                proxy._m_errors.inc()
                return "error", (422, str(e), {})
            from ..query import ast as _ast

            if cluster is not None and isinstance(
                stmt, (_ast.CreateTable, _ast.DropTable)
            ):
                # Cluster DDL goes through the coordinator: IT picks the
                # owning shard/node and dispatches the actual create
                # (ref: meta_based TableManipulator, write.rs:176-263).
                # The request's budget rides into the meta hop: the
                # meta client caps each failover attempt at
                # min(its timeout, remaining) and refuses once drained.
                def ddl():
                    from ..utils.deadline import deadline_scope

                    with deadline_scope(deadline):
                        if isinstance(stmt, _ast.CreateTable):
                            return cluster.meta.create_table(stmt.table, query)
                        return cluster.meta.drop_table(stmt.table)

                try:
                    await loop.run_in_executor(None, ddl)
                except Exception as e:
                    from ..utils.deadline import DeadlineExceeded

                    if isinstance(e, DeadlineExceeded):
                        return "error", (
                            504, str(e),
                            {"kind": "deadline", "retry_after_s": 1.0},
                        )
                    # The coordinator already implements IF NOT EXISTS /
                    # IF EXISTS leniency, so any error here is REAL —
                    # never report success for DDL that happened nowhere.
                    return "error", (422, str(e), {})
                return "affected", 0
            if cluster is not None and isinstance(stmt, _ast.Insert):
                fence = _write_fence(cluster, router, stmt.table)
                if fence is not None:
                    return "error", (*fence, {})
            table = _table_of_statement(stmt)
            if table is not None and table.lower().startswith("system."):
                # Virtual introspection tables (system.public.query_stats,
                # .metrics, .tables) answer about THE NODE YOU ASKED —
                # forwarding them by name hash would silently serve a
                # different node's state.
                table = None
            if table is not None:
                route = router.route(table)
                if not route.is_local:
                    # Scale-out read path: a node holding a READ REPLICA
                    # of the shard serves eligible bounded-staleness
                    # SELECTs locally instead of forwarding them all to
                    # the one leader (cluster/replica).
                    if (
                        cluster is not None
                        and _follower_reads_enabled()
                        and _replica_select(stmt) is not None
                    ):
                        served = await self._try_replica_local(
                            query, tenant, table, replica_read,
                            staleness_ms, replica_epoch, deadline, wire,
                        )
                        if served is not None:
                            return served
                    if already_forwarded:
                        return "error", (
                            502,
                            f"routing loop: {table!r} routed to "
                            f"{route.endpoint} but this node also received "
                            "it forwarded",
                            {},
                        )
                    if (
                        cluster is not None
                        and route.replicas
                        and not replica_read
                        and _follower_reads_enabled()
                        and _replica_select(stmt) is not None
                    ):
                        # offload to the least-loaded follower; a typed
                        # refusal (stale/fenced) falls back to the leader
                        served = await self._forward_replica(
                            route, query, staleness_ms, deadline
                        )
                        if served is not None:
                            return served
                    return await self._forward(route.endpoint, query, deadline)
                local_route = route if route.replicas else None
            else:
                local_route = None
        else:
            local_route = None
        if query.lstrip()[:7].lower().startswith("select"):
            # tenant is part of the key: a follower must not skip ITS
            # tenant's quota charge by riding another tenant's flight
            # (the proxy-level dedup charges before coalescing instead)
            key = (self._write_epoch, tenant, query.strip())
            running = self._inflight.get(key)
            if running is not None and not running.done():
                self._m_deduped.inc()
                # count into the wlm dedup family too so the workload
                # table reflects gateway-level coalescing
                self.app["proxy"].wlm.dedup.note_coalesced()
                out = await self._await_flight(running, deadline, leader=False)
                return await self._maybe_shed_to_follower(
                    out, local_route, query, staleness_ms, replica_read,
                    deadline,
                )
            # ensure_future (not a bare await): the shared execution must
            # outlive a cancelled leader request so followers still get
            # their result
            task = asyncio.ensure_future(
                self._run_local(proxy, query, tenant, deadline, wire)
            )
            self._inflight[key] = task

            def _done(t, key=key):
                if self._inflight.get(key) is t:
                    self._inflight.pop(key, None)

            task.add_done_callback(_done)
            out = await self._await_flight(task, deadline, leader=True)
            return await self._maybe_shed_to_follower(
                out, local_route, query, staleness_ms, replica_read,
                deadline,
            )
        # any non-SELECT may change visible state: advance the epoch so
        # later reads start a fresh execution. Bumped AFTER the statement
        # runs (conservatively even when it fails) — bumping before
        # would let a post-commit SELECT join a pre-write flight that
        # became leader under the already-advanced epoch.
        try:
            return await self._run_local(proxy, query, tenant, deadline, wire)
        finally:
            self._write_epoch += 1

    async def _await_flight(self, task, deadline, leader: bool):
        """Await a (shielded) gateway single-flight execution under the
        caller's OWN budget. A follower whose budget drains answers its
        typed 504 while the flight keeps running for everyone else; a
        LEADER whose client disconnects — with nobody else coalesced on
        the flight — flips the cancel flag so the worker-thread
        execution unwinds at its next checkpoint and releases its
        admission slot (the proxy-level dedup converts that into a
        typed retryable error for any thread-level followers — never a
        QueryCancelled for a query THEY didn't cancel)."""
        if not leader:
            task._hdb_followers = getattr(task, "_hdb_followers", 0) + 1
        rem = deadline.remaining_s() if deadline is not None else None
        try:
            if rem is None:
                out = await asyncio.shield(task)
            else:
                try:
                    out = await asyncio.wait_for(asyncio.shield(task), rem)
                except asyncio.TimeoutError:
                    # the worker thread observes the SAME Deadline
                    # object at its next checkpoint and unwinds with
                    # the typed error + ledger marks + expiry counter
                    # on its own; the gateway just answers now
                    return "error", (
                        504,
                        f"query exceeded its {deadline.budget_ms:.0f}ms "
                        "time budget",
                        {"kind": "deadline", "retry_after_s": 1.0},
                    )
            if not leader and isinstance(out, tuple) and out[0] == "error":
                # a coalesced follower never surfaces the LEADER's
                # personal ending (its budget, its kill) — same
                # contract as the proxy-level dedup/_member_error: a
                # typed retryable overload instead, a retry starts a
                # fresh flight
                kind = out[1][2].get("kind")
                if kind in ("deadline", "cancelled"):
                    return "error", (
                        503,
                        "the in-flight leader serving this read "
                        f"ended early ({kind}); retry starts a fresh "
                        "execution",
                        {"kind": "overloaded", "retry_after_s": 0.1},
                    )
            return out
        except asyncio.CancelledError:
            # client disconnect: cooperative cancel — the shielded task
            # survives for coalesced followers; a leader with NO ONE
            # else waiting cancels the in-flight execution instead of
            # leaving it immortal
            if (
                leader
                and deadline is not None
                and not getattr(task, "_hdb_followers", 0)
            ):
                deadline.cancel("disconnect")
                from ..utils.deadline import note_cancel

                note_cancel("disconnect")
            raise
        finally:
            if not leader:
                task._hdb_followers = getattr(task, "_hdb_followers", 1) - 1

    async def _run_local(
        self, proxy, query: str, tenant: str = "default", deadline=None,
        wire: str = "http",
    ):
        """Run the statement on this node. -> ``Served``: ``(kind,
        payload)`` plus the id of the statement's ``sql`` trace, which
        ``Proxy.handle_sql`` stamps on the span that waits for it (a wire
        handler's ``handle``; no handler trace, no id)."""
        from ..utils.tracectx import current_span

        served = Served(
            await self._serve_local(proxy, query, tenant, deadline, wire)
        )
        waiting = current_span()
        if waiting is not None:
            served.request_id = waiting.attrs.get("request_id")
        return served

    async def _serve_local(
        self, proxy, query: str, tenant: str, deadline, wire: str
    ):
        from ..utils.deadline import DeadlineExceeded, QueryCancelled, bind

        loop = asyncio.get_running_loop()

        def run():
            # positional call keeps handle_sql wrappers/monkeypatches with
            # the historical (sql) signature working
            if tenant == "default":
                out = proxy.handle_sql(query)
            else:
                out = proxy.handle_sql(query, tenant=tenant)
            return _answer_of(out, wire)

        # the request deadline rides a context COPY into the worker
        # thread (handle_sql picks it up via current_deadline()) so the
        # historical signature stays intact for wrappers/monkeypatches
        ctx = bind(deadline)
        try:
            out = await loop.run_in_executor(None, ctx.run, run)
        except DeadlineExceeded as e:
            return "error", (
                504, str(e),
                {"kind": "deadline", "retry_after_s": e.retry_after_s},
            )
        except QueryCancelled as e:
            # 499-style: the nginx "client closed request" convention —
            # the work was cooperatively stopped, not server-failed
            return "error", (499, str(e), {"kind": "cancelled"})
        except BlockedError as e:
            return "error", (403, str(e), {"kind": "blocked"})
        except OverloadedError as e:
            # admission shed: healthy but full — retryable by contract
            return "error", (
                503, str(e),
                {"kind": "overloaded", "retry_after_s": e.retry_after_s},
            )
        except QuotaExceededError as e:
            return "error", (
                429, str(e),
                {"kind": "quota", "retry_after_s": e.retry_after_s},
            )
        except Exception as e:  # parse/plan/execution errors -> 422 like ref
            return "error", (422, str(e), {})
        if isinstance(out, AffectedRows):
            return "affected", out.count
        return "rows", out

    async def _try_replica_local(
        self,
        query: str,
        tenant: str,
        table: str,
        replica_read: bool,
        staleness_ms: Optional[int],
        replica_epoch: Optional[int],
        deadline=None,
        wire: str = "http",
    ):
        """Serve an eligible SELECT from THIS node's read-only follower
        handle. Returns a gateway result, or None meaning "not servable
        here — route normally" (locally-received reads fall through to
        the leader forward; a FORWARDED replica read instead gets the
        typed retryable refusal so the origin performs the fallback)."""
        from ..cluster.replica import (
            REPLICA_RESPONSE,
            ReplicaFencedError,
            ReplicaStaleError,
            note_replica_read,
            replica_serving,
        )

        app = self.app
        cluster = app["cluster"]
        conn = app["conn"]
        proxy = app["proxy"]
        if cluster is None or not cluster.serves_replica(table):
            if replica_read:
                note_replica_read("fenced")
                return "error", (
                    503,
                    f"table {table!r} not replicated on this node",
                    {"kind": "replica_fenced", "retry_after_s": 1.0},
                )
            return None
        if staleness_ms is None:
            staleness_ms = app.get("read_staleness_ms") or 0

        def serve():
            import time as _time

            epoch, data = cluster.replica_read_state(
                table, expected_epoch=replica_epoch
            )
            from ..query import plan as plan_mod

            plan = conn._cached_plan(query)
            inner = (
                plan.inner if isinstance(plan, plan_mod.ExplainPlan) else plan
            )
            if not isinstance(inner, plan_mod.QueryPlan) or inner.table != table:
                raise ReplicaStaleError(
                    "statement shape not replica-servable", epoch=epoch
                )
            end = inner.predicate.time_range.exclusive_end
            wm = data.follower_watermark_ms()
            if end > wm:
                # opportunistic catch-up before refusing: the tail loop
                # may simply not have run since the leader's last flush
                try:
                    data.refresh_from_manifest()
                    wm = data.follower_watermark_ms()
                except Exception:
                    pass
            now_ms = int(_time.time() * 1000)
            lag_ms = max(0, now_ms - wm) if wm > 0 else now_ms
            # Bounded-staleness predicate: the range must be entirely
            # below the watermark, OR the caller opted into a staleness
            # bound the follower currently satisfies. A fresh open-tail
            # range on a lagging follower always refuses.
            if end > wm and not (
                staleness_ms and wm > 0 and lag_ms <= staleness_ms
            ):
                raise ReplicaStaleError(
                    f"time range end {end} beyond follower watermark {wm} "
                    f"for {table!r} (lag {lag_ms}ms)",
                    epoch=epoch,
                    watermark_ms=wm,
                )
            with replica_serving(table, epoch, lag_ms):
                if tenant == "default":
                    out = proxy.handle_sql(query)
                else:
                    out = proxy.handle_sql(query, tenant=tenant)
            return _answer_of(out, wire), epoch, lag_ms

        loop = asyncio.get_running_loop()
        from ..utils.deadline import bind

        ctx = bind(deadline)
        try:
            out, epoch, lag_ms = await loop.run_in_executor(
                None, ctx.run, serve
            )
        except ReplicaStaleError as e:
            if replica_read:
                # the ORIGIN owns the leader fallback for forwarded reads
                return "error", (
                    503, str(e),
                    {"kind": "replica_stale", "retry_after_s": e.retry_after_s},
                )
            note_replica_read("stale_fallback")
            return None  # fall through to the leader forward
        except ReplicaFencedError as e:
            note_replica_read("fenced")
            if replica_read:
                return "error", (
                    503, str(e),
                    {"kind": "replica_fenced",
                     "retry_after_s": e.retry_after_s},
                )
            return None
        except BlockedError as e:
            return "error", (403, str(e), {"kind": "blocked"})
        except OverloadedError as e:
            return "error", (
                503, str(e),
                {"kind": "overloaded", "retry_after_s": e.retry_after_s},
            )
        except QuotaExceededError as e:
            return "error", (
                429, str(e),
                {"kind": "quota", "retry_after_s": e.retry_after_s},
            )
        except Exception as e:
            from ..utils.deadline import DeadlineExceeded, QueryCancelled

            if isinstance(e, DeadlineExceeded):
                return "error", (
                    504, str(e),
                    {"kind": "deadline", "retry_after_s": e.retry_after_s},
                )
            if isinstance(e, QueryCancelled):
                return "error", (499, str(e), {"kind": "cancelled"})
            return "error", (422, str(e), {})
        note_replica_read("served")
        # visible to the HTTP handler (same request task context): the
        # response advertises the epoch + lag it was served at
        REPLICA_RESPONSE.set({"epoch": epoch, "lag_ms": lag_ms})
        if isinstance(out, AffectedRows):  # defensive: SELECTs only
            return "affected", out.count
        return "rows", out

    async def _forward_replica(
        self, route, query: str, staleness_ms: Optional[int], deadline=None
    ):
        """Offload an eligible SELECT to one of the route's follower
        replicas. Returns a gateway result, or None meaning "use the
        leader" (no replica available, or the follower refused with the
        typed stale/fenced error — the refusal is the follower telling
        us the leader owns this read)."""
        import aiohttp

        from ..cluster.replica import note_replica_read

        router = self.app["router"]
        pick = getattr(router, "pick_replica", None)
        target = (
            pick(route, exclude=getattr(router, "self_endpoint", ""))
            if pick is not None
            else None
        )
        if target is None:
            return None
        headers = {
            FORWARD_HEADER: "1",
            REPLICA_READ_HEADER: "1",
            REPLICA_EPOCH_HEADER: str(route.epoch),
            # the REMAINING budget rides the hop; the follower refuses
            # already-expired work and charges the rest
            **_budget_headers(deadline),
        }
        if staleness_ms:
            headers[STALENESS_HEADER] = f"{int(staleness_ms)}ms"
        try:
            session = await _client_session(self.app)
            async with session.post(
                f"http://{target}/sql",
                json={"query": query},
                headers=headers,
                timeout=_forward_client_timeout(self.app, deadline),
            ) as resp:
                body = await resp.json(content_type=None)
        except (aiohttp.ClientError, asyncio.TimeoutError, ValueError):
            return None  # follower unreachable: the leader still can
        if resp.status != 200:
            if isinstance(body, dict) and body.get("replica"):
                # typed stale/fenced refusal — fall back to the leader
                note_replica_read("stale_fallback")
            # ANY follower failure falls back: the leader is
            # authoritative and could serve the read (a genuine query
            # error reproduces there with the authoritative message) —
            # surfacing a follower-side 502/422 would fail reads the
            # pre-replica path served fine
            return None
        if "affected_rows" in body:
            return "affected", body["affected_rows"]
        rows = body.get("rows", [])
        names = body.get("names") or (list(rows[0].keys()) if rows else [])
        return "rows", SqlAnswer(names, rows=rows)

    async def _maybe_shed_to_follower(
        self, out, local_route, query: str,
        staleness_ms: Optional[int], replica_read: bool, deadline=None,
    ):
        """Leader-overload relief: when the LOCAL leader shed an eligible
        SELECT with the retryable OverloadedError and the shard has
        follower replicas, try one replica before surfacing the shed to
        the client. The follower still applies its own staleness/fencing
        rules; a refusal returns the original shed error."""
        if (
            local_route is None
            or replica_read
            or not _follower_reads_enabled()
            or not (isinstance(out, tuple) and out[0] == "error")
        ):
            return out
        status, msg, extra = out[1]
        if extra.get("kind") != "overloaded":
            return out
        served = await self._forward_replica(
            local_route, query, staleness_ms, deadline
        )
        return served if served is not None else out

    async def _forward(self, endpoint: str, query: str, deadline=None):
        """Ship the statement to the owning node's /sql (ref: forward.rs).

        The per-call timeout is min([limits] forward_timeout, the
        request's remaining budget) and the hop re-stamps the budget
        header — a chain of forwards decrements ONE budget instead of
        burning a fixed 30s per hop."""
        import aiohttp

        try:
            session = await _client_session(self.app)
            async with session.post(
                f"http://{endpoint}/sql",
                json={"query": query},
                headers={FORWARD_HEADER: "1", **_budget_headers(deadline)},
                timeout=_forward_client_timeout(self.app, deadline),
            ) as resp:
                body = await resp.json(content_type=None)
        except asyncio.TimeoutError:
            if deadline is not None and deadline.expired():
                from ..utils.deadline import note_expired

                note_expired("forward")
                return "error", (
                    504,
                    f"forward to {endpoint} outlived the query's "
                    f"{deadline.budget_ms:.0f}ms time budget",
                    {"kind": "deadline", "retry_after_s": 1.0},
                )
            return "error", (502, f"forward to {endpoint} timed out", {})
        except (aiohttp.ClientError, ValueError) as e:
            # ValueError covers non-JSON bodies; failures map to the
            # same 502 contract, not unwind wire-protocol sessions.
            return "error", (502, f"forward to {endpoint} failed: {e}", {})
        if resp.status != 200:
            # a typed deadline/cancel ending on the remote hop keeps
            # its kind so MySQL/PG map their native codes, not a
            # generic internal error
            extra: dict = {}
            if resp.status == 504:
                extra = {"kind": "deadline", "retry_after_s": 1.0}
            elif resp.status == 499:
                extra = {"kind": "cancelled"}
            return "error", (
                resp.status, body.get("error", "forward failed"), extra,
            )
        if "affected_rows" in body:
            return "affected", body["affected_rows"]
        rows = body.get("rows", [])
        names = body.get("names") or (list(rows[0].keys()) if rows else [])
        return "rows", SqlAnswer(names, rows=rows)


@web.middleware
async def _auth_middleware(request: web.Request, handler):
    """Bearer-token gate on the admin/debug surface (ref: proxy/src/auth/
    — the data plane stays open like the reference's default; operators
    set server.auth_token to lock down the control surface)."""
    token = request.app.get("auth_token")
    if token and (
        request.path.startswith("/admin/") or request.path.startswith("/debug/")
    ):
        import hmac

        supplied = request.headers.get("Authorization", "")
        if not hmac.compare_digest(supplied, f"Bearer {token}"):
            return web.json_response({"error": "unauthorized"}, status=401)
    return await handler(request)


def create_app(
    conn: Connection, router=None, cluster=None, auth_token: str = "",
    limits=None, observability=None, node: str = "standalone",
    rules_cfg=None, slo_cfg=None, read_staleness_s: float = 0.0,
    batch_cfg=None,
) -> web.Application:
    """``cluster``: a ClusterImpl when this node runs under a coordinator;
    adds the /meta_event endpoints, meta-driven DDL, and write fencing.
    ``limits``: a config LimitsConfig for the workload manager's knobs
    (admission slots/queue/deadline/memory budget, dedup).
    ``batch_cfg``: a config [wlm.batch] BatchSection — when enabled, the
    proxy gathers shape-identical in-flight SELECTs for a micro-batching
    window and serves each cohort with one fused device dispatch
    (wlm/batch); None/disabled reproduces the plain single-flight path.
    ``observability``: a config ObservabilitySection; when its
    ``self_scrape`` is on, the node runs the self-monitoring recorder
    (engine/metrics_recorder) that periodically writes its own metrics
    registry into ``system_metrics.samples`` through the normal write
    path, rows labeled ``node``.
    ``rules_cfg``: a config RulesSection; when enabled the node runs the
    continuous-query engine (rules/) — recording rules, tiered rollups
    with transparent query rewriting, and the alert evaluator — with
    /admin/rules and /debug/alerts as its control surface.
    ``slo_cfg``: a config SloSection; objectives make the node grade its
    own service levels (slo/) — the evaluator rides the rules engine's
    cadence and serves verdicts at /debug/slo and system.public.slo.
    In coordinator mode the recorder and rules engine now run too:
    their output tables are created through the coordinator's
    meta-serialized DDL instead of the local catalog."""
    import time as _time

    proxy = Proxy(conn, limits=limits, batch_cfg=batch_cfg)
    app = web.Application(middlewares=[_auth_middleware])
    app["auth_token"] = auth_token
    app["conn"] = conn
    app["proxy"] = proxy
    app["router"] = router
    app["cluster"] = cluster
    app["node"] = node
    # default bounded-staleness opt-in for follower reads ([cluster]
    # read_staleness; per-request override via X-HoraeDB-Read-Staleness)
    app["read_staleness_ms"] = int(max(0.0, read_staleness_s) * 1000)
    # deadline plane (utils/deadline): the default per-query budget and
    # the per-hop forwarding cap — X-HoraeDB-Timeout-Ms / the MySQL+PG
    # session knobs override the budget per request
    app["query_timeout_ms"] = (
        getattr(limits, "query_timeout_s", 60.0) if limits is not None
        else 60.0
    ) * 1000.0
    app["forward_timeout_s"] = (
        getattr(limits, "forward_timeout_s", 30.0) if limits is not None
        else 30.0
    )
    app["started_at"] = _time.time()
    app.on_cleanup.append(_close_client_session)

    if observability is not None:
        # Bounded event-journal capacity ([observability] event_ring):
        # applied to the process-global ring; drops are accounted in
        # horaedb_events_dropped_total and surfaced in /debug/status.
        from ..utils.events import EVENT_STORE

        EVENT_STORE.resize(observability.event_ring)
        # ...and the decision journal ([observability] decision_ring):
        # same accounting contract, horaedb_decision_dropped_total.
        from ..obs.decisions import DECISION_JOURNAL

        DECISION_JOURNAL.resize(observability.decision_ring)
        # ...and the profile plane ([observability] profile_keys) plus
        # the finished-trace rings (trace_ring / trace_slow_ring):
        # horaedb_profile_dropped_total accounts key evictions.
        from ..obs.profile import PROFILE
        from ..utils.tracectx import TRACE_STORE

        PROFILE.resize(getattr(observability, "profile_keys", 1024))
        TRACE_STORE.resize(
            recent=getattr(observability, "trace_ring", 64),
            slow=getattr(observability, "trace_slow_ring", 256),
        )

    recorder = None
    if observability is not None and observability.self_scrape:
        from ..engine.metrics_recorder import MetricsRecorder

        # Coordinator mode included: the recorder creates the samples
        # table through the coordinator's meta-serialized DDL (the old
        # colliding-table-id hazard of local creation) and forwards
        # non-owner rounds to the meta-assigned owner.
        recorder = MetricsRecorder(
            conn,
            interval_s=observability.self_scrape_interval_s,
            retention_s=observability.self_metrics_retention_s,
            node=node,
            router=router,
            cluster=cluster,
        )

        async def _start_recorder(app_):
            recorder.start()

        async def _stop_recorder(app_):
            recorder.close()

        app.on_startup.append(_start_recorder)
        app.on_cleanup.append(_stop_recorder)
    app["metrics_recorder"] = recorder

    slo_eval = None
    if slo_cfg is not None and slo_cfg.objectives:
        from ..slo import SloEvaluator

        slo_eval = SloEvaluator(conn, slo_cfg, node=node)
        if rules_cfg is None or not rules_cfg.enabled:
            logger.warning(
                "[slo] objectives configured but the rules engine is "
                "disabled — the SLO evaluator rides its cadence and will "
                "never tick"
            )
    app["slo"] = slo_eval

    rule_engine = None
    if rules_cfg is not None and rules_cfg.enabled:
        from ..rules import RuleEngine

        rule_engine = RuleEngine(
            conn, rules_cfg, node=node, router=router, cluster=cluster,
            slo=slo_eval,
        )

        async def _start_rules(app_):
            rule_engine.start()

        async def _stop_rules(app_):
            rule_engine.close()

        app.on_startup.append(_start_rules)
        app.on_cleanup.append(_stop_rules)
    app["rule_engine"] = rule_engine

    # Readiness warmup: tables open (and replay their WAL) lazily, so a
    # fresh node would report wal_replay_done=True before any replay
    # ever started — open every LOCALLY-OWNED registered table in the
    # background and gate readiness on completion. Standalone owns
    # everything; static-cluster warms only tables the router places
    # here (opening unowned tables would replay another node's WAL);
    # coordinator mode skips — its shard machinery opens owned tables
    # eagerly on shard assignment.
    app["warmup_done"] = cluster is not None
    if not app["warmup_done"]:
        _warm_names = [
            n for n in conn.catalog.table_names()
            if router is None or router.route(n).is_local
        ]
        if not _warm_names:
            app["warmup_done"] = True
        else:
            import threading as _threading

            def _warm(names=_warm_names):
                for nm in names:
                    try:
                        conn.catalog.open(nm)
                    except Exception:
                        logger.exception("readiness warmup: open %r failed", nm)
                app["warmup_done"] = True

            _threading.Thread(
                target=_warm, name="wal-warmup", daemon=True
            ).start()

    async def _close_proxy(app_):
        app_["proxy"].close()

    app.on_cleanup.append(_close_proxy)

    async def _forward_if_remote(request: web.Request, table) -> Optional[web.Response]:
        """Proxy the raw request to the owning node (ref: forward.rs).

        Returns None when the table is local (or routing is off). A request
        that has already been forwarded once is never forwarded again —
        misconfigured topologies surface as an error, not a loop.
        """
        if router is None or table is None:
            return None
        route = router.route(table)
        if route.is_local:
            return None
        if request.headers.get(FORWARD_HEADER):
            return web.json_response(
                {
                    "error": (
                        f"routing loop: {table!r} routed to {route.endpoint} "
                        "but this node also received it forwarded"
                    )
                },
                status=502,
            )
        import aiohttp

        from ..utils.deadline import Deadline

        body = await request.read()
        url = f"http://{route.endpoint}{request.path_qs}"
        # a client-sent budget rides the hop (re-stamped with what
        # remains) and caps the per-call timeout below [limits]
        # forward_timeout; an explicit zero/negative budget is
        # "already expired" — refuse it here like the /sql path does
        raw_budget = _parse_timeout_ms(request.headers.get(TIMEOUT_HEADER))
        if raw_budget is not None and raw_budget <= 0:
            from ..utils.deadline import note_expired

            note_expired("ingress")
            return web.json_response(
                {"error": "request arrived with an exhausted time budget"},
                status=504,
            )
        fwd_deadline = Deadline(raw_budget)
        try:
            session = await _client_session(request.app)
            async with session.post(
                url,
                data=body,
                headers={
                    FORWARD_HEADER: "1",
                    "Content-Type": request.headers.get(
                        "Content-Type", "application/json"
                    ),
                    **_budget_headers(fwd_deadline),
                },
                timeout=_forward_client_timeout(request.app, fwd_deadline),
            ) as resp:
                payload = await resp.read()
                return web.Response(
                    body=payload,
                    status=resp.status,
                    content_type=resp.content_type,
                )
        except asyncio.TimeoutError:
            # budget-capped hop timed out: with a client budget that is
            # 504 (the work may finish on the owner, but the caller's
            # time is gone); without one it is the ordinary 502
            status = 504 if fwd_deadline.expired() else 502
            return web.json_response(
                {"error": f"forward to {route.endpoint} timed out"},
                status=status,
            )
        except aiohttp.ClientError as e:
            return web.json_response(
                {"error": f"forward to {route.endpoint} failed: {e}"}, status=502
            )

    # ---- core ----------------------------------------------------------
    gateway = SqlGateway(app)
    app["sql_gateway"] = gateway

    async def sql(request: web.Request) -> web.Response:
        """POST /sql under the handler's own root ``http_sql``: the
        server's part of what a client calls the wire. It folds into
        /debug/profile and takes no place in the /debug/trace ring, which
        keeps the ``sql`` traces (``sql``'s extent is not moved)."""
        from ..utils.tracectx import finish_trace, start_trace

        trace, handle = start_trace(None, "http_sql")
        trace.route = "http"
        try:
            return await _sql(request, trace)
        finally:
            finish_trace(handle, store=False)

    async def _sql(request: web.Request, trace) -> web.Response:
        from ..utils.tracectx import span

        with span("accept") as sp:
            try:
                body = await request.json()
            except json.JSONDecodeError:
                return web.json_response({"error": "invalid JSON body"}, status=400)
            sp.set(bytes=request.content_length or 0)
        query = body.get("query")
        if not isinstance(query, str) or not query.strip():
            return web.json_response({"error": "missing 'query'"}, status=400)
        from ..cluster.replica import REPLICA_RESPONSE

        # keep-alive connections reuse one handler task (one context):
        # clear before executing or a later statement on the same
        # connection would inherit the previous one's replica headers
        REPLICA_RESPONSE.set(None)
        # ``handle``: the hop onto the worker thread and the whole ``sql``
        # trace (and ``rows`` under it), so the root's own time stays small
        with span("handle"):
            served = await gateway.execute(
                query,
                already_forwarded=bool(request.headers.get(FORWARD_HEADER)),
                protocol="http",
                # per-tenant quota scope (wlm/quota); absent -> "default"
                tenant=request.headers.get("X-HoraeDB-Tenant", "default"),
                replica_read=bool(request.headers.get(REPLICA_READ_HEADER)),
                staleness_ms=_parse_staleness(
                    request.headers.get(STALENESS_HEADER)
                ),
                replica_epoch=(
                    int(request.headers[REPLICA_EPOCH_HEADER])
                    if request.headers.get(REPLICA_EPOCH_HEADER, "").isdigit()
                    else None
                ),
                # per-request time budget (forwarding hops re-stamp the
                # remaining budget into the same header)
                timeout_ms=_parse_timeout_ms(request.headers.get(TIMEOUT_HEADER)),
            )
        kind, payload = served
        headers = {}
        request_id = getattr(served, "request_id", None)
        if request_id is not None:
            trace.trace_id = request_id
            trace.root.set(request_id=request_id)
            headers[REQUEST_ID_HEADER] = str(request_id)
        if kind == "error":
            status, msg, extra = payload
            if extra.get("retry_after_s") is not None:
                # shed/quota answers are retryable by contract: say when
                headers["Retry-After"] = str(
                    max(1, int(round(extra["retry_after_s"])))
                )
            body = {"error": msg}
            if extra.get("kind") in ("replica_stale", "replica_fenced"):
                # typed refusal marker: the forwarding origin falls back
                # to the leader on it instead of failing the client
                body["replica"] = extra["kind"]
            return web.json_response(body, status=status, headers=headers)
        rinfo = REPLICA_RESPONSE.get()
        if rinfo is not None:
            # follower-served: advertise the manifest epoch + lag
            headers[REPLICA_EPOCH_HEADER] = str(rinfo["epoch"])
            headers["X-HoraeDB-Replica-Lag-Ms"] = str(rinfo["lag_ms"])
        if kind == "affected":
            return web.json_response(
                {"affected_rows": payload}, headers=headers
            )
        with span("encode") as sp:
            body = payload.body()
            sp.set(bytes=len(body))
        return web.Response(
            body=body, content_type="application/json", charset="utf-8",
            headers=headers,
        )

    async def write(request: web.Request) -> web.Response:
        try:
            body = await request.json()
            table = body["table"]
            rows = body["rows"]
        except Exception:
            body, table, rows = None, None, None
        if not isinstance(table, str) or not isinstance(rows, list) or not rows \
                or not all(isinstance(r, dict) for r in rows):
            return web.json_response(
                {"error": "body must be {'table': t, 'rows': [{...}]}"}, status=400
            )
        if cluster is not None:
            fence = _write_fence(cluster, router, table)
            if fence is not None:
                status, msg = fence
                return web.json_response({"error": msg}, status=status)
        forwarded = await _forward_if_remote(request, table)
        if forwarded is not None:
            return forwarded
        conn_ = request.app["conn"]
        # ?nonblocking=1: shed instantly at the write-stall bound instead
        # of blocking out the stall deadline — the contract forwarded
        # self-scrape writes need (engine/metrics_recorder._forward): the
        # 503 below IS the owner's stall shed, and the owner must not tie
        # up an executor thread for a telemetry round it would shed anyway.
        nonblocking = _query_flag(request, "nonblocking")

        def do_write():
            from ..utils.tracectx import owned_trace

            # the write path's own root: the engine's write_wait /
            # write_group / wal_append / memtable_write spans hang here
            with owned_trace("write", route="ingest", shape=f"insert {table}"):
                proxy.limiter.check(table)
                proxy.wlm.quota.charge_write("default", table, len(rows))
                t = conn_.catalog.open(table)
                if t is None:
                    raise ValueError(f"table not found: {table}")
                from ..common_types.row_group import RowGroup
                from ..engine.instance import nonblocking_backpressure

                rg = RowGroup.from_rows(t.schema, rows)
                if nonblocking:
                    with nonblocking_backpressure():
                        t.write(rg)
                else:
                    t.write(rg)
                proxy.hotspot.record(table, True)
                return len(rg)

        try:
            n = await asyncio.get_running_loop().run_in_executor(None, do_write)
        except BlockedError as e:
            return web.json_response({"error": str(e)}, status=403)
        except OverloadedError as e:
            # write-stall shed (engine backpressure): healthy but full —
            # same retryable contract as an admission shed
            return web.json_response(
                {"error": str(e)}, status=503,
                headers={"Retry-After": str(max(1, int(round(e.retry_after_s))))},
            )
        except QuotaExceededError as e:
            return web.json_response(
                {"error": str(e)}, status=429,
                headers={"Retry-After": str(max(1, int(round(e.retry_after_s))))},
            )
        except Exception as e:
            return web.json_response({"error": str(e)}, status=422)
        # a raw write changes visible state: later identical SELECTs must
        # not join a pre-write single-flight execution (either layer)
        gateway._write_epoch += 1
        proxy.wlm.dedup.bump_epoch()
        return web.json_response({"affected_rows": n})

    async def _follower_protocol(
        request: web.Request,
        tables: list,
        end_ms: Optional[int],
        proto: str,
        run_local,
        respond,
    ) -> Optional[web.Response]:
        """Follower routing for the non-SQL read wires (PromQL /
        InfluxQL / OpenTSDB) — the same serve-locally / offload-to-
        replica / fall-back-to-leader discipline the SQL gateway runs,
        with ``route=follower`` stamped into ``system.public.query_stats``.

        Returns a Response when a replica served (or typed-refused a
        forwarded replica read), or None meaning "handle normally" —
        local evaluation or the ordinary leader forward. ``end_ms`` is
        the exclusive upper time bound the query needs covered;
        ``run_local`` evaluates the query (worker thread), ``respond``
        wraps its output into the protocol's response shape."""
        from ..cluster.replica import (
            ReplicaFencedError,
            ReplicaStaleError,
            note_replica_read,
            replica_serving,
        )

        replica_read = bool(request.headers.get(REPLICA_READ_HEADER))
        if (
            router is None
            or cluster is None
            or not _follower_reads_enabled()
            or not tables
        ):
            if replica_read:
                # a forwarded replica read must get the TYPED refusal so
                # the origin falls back to the leader, never a silent
                # unfenced local evaluation
                return web.json_response(
                    {
                        "error": f"{proto} read not replica-servable here",
                        "replica": "replica_fenced",
                    },
                    status=503,
                )
            return None
        staleness_ms = _parse_staleness(request.headers.get(STALENESS_HEADER))
        if staleness_ms is None:
            staleness_ms = request.app.get("read_staleness_ms") or 0
        epoch_hdr = request.headers.get(REPLICA_EPOCH_HEADER, "")
        expected_epoch = int(epoch_hdr) if epoch_hdr.isdigit() else None

        if all(cluster.serves_replica(t) for t in tables):

            def serve():
                import time as _time

                from ..utils.querystats import finish_ledger, start_ledger

                worst_lag = 0
                epoch0 = 0
                for i, t in enumerate(tables):
                    epoch, data = cluster.replica_read_state(
                        t,
                        expected_epoch=(
                            expected_epoch if len(tables) == 1 else None
                        ),
                    )
                    if i == 0:
                        epoch0 = epoch
                    wm = data.follower_watermark_ms()
                    if end_ms is None or end_ms > wm:
                        # opportunistic catch-up before refusing (the
                        # tail loop may not have run since the last flush)
                        try:
                            data.refresh_from_manifest()
                            wm = data.follower_watermark_ms()
                        except Exception:
                            pass
                    now_ms = int(_time.time() * 1000)
                    lag_ms = max(0, now_ms - wm) if wm > 0 else now_ms
                    covered = end_ms is not None and end_ms <= wm
                    if not covered and not (
                        staleness_ms and wm > 0 and lag_ms <= staleness_ms
                    ):
                        raise ReplicaStaleError(
                            f"{proto} read needs data beyond follower "
                            f"watermark {wm} for {t!r} (lag {lag_ms}ms)",
                            epoch=epoch,
                            watermark_ms=wm,
                        )
                    worst_lag = max(worst_lag, lag_ms)
                # one ledger per served statement, like the SQL proxy —
                # query_stats carries route=follower + replica_lag_ms
                ledger, tok = start_ledger(None, f"{proto}: {tables[0]}")
                t0 = _time.perf_counter()
                try:
                    with replica_serving(tables[0], epoch0, worst_lag):
                        out = run_local()
                except BaseException:
                    # a failed evaluation was NOT follower-served: close
                    # the ledger without recording, or query_stats (and
                    # the elastic load signal reading it) would carry a
                    # phantom route=follower row for a query the normal
                    # path re-runs
                    finish_ledger(ledger, tok, 0.0, record_stats=False)
                    raise
                ledger.set_route("follower")
                ledger.set_table(tables[0])
                ledger.add(replica_lag_ms=worst_lag)
                finish_ledger(ledger, tok, _time.perf_counter() - t0)
                return out, epoch0, worst_lag

            loop = asyncio.get_running_loop()
            try:
                out, epoch, lag_ms = await loop.run_in_executor(None, serve)
            except ReplicaStaleError as e:
                if replica_read:
                    return web.json_response(
                        {"error": str(e), "replica": "replica_stale"},
                        status=503,
                        headers={"Retry-After": "1"},
                    )
                note_replica_read("stale_fallback")
                return None  # leader path serves it
            except ReplicaFencedError as e:
                note_replica_read("fenced")
                if replica_read:
                    return web.json_response(
                        {"error": str(e), "replica": "replica_fenced"},
                        status=503,
                        headers={"Retry-After": "1"},
                    )
                return None
            except Exception as e:
                if replica_read:
                    # ANY follower-side failure maps to the typed
                    # fallback contract — a genuine query error
                    # reproduces on the leader with the authoritative
                    # message (same stance as _forward_replica)
                    return web.json_response(
                        {"error": str(e), "replica": "replica_stale"},
                        status=503,
                    )
                return None
            note_replica_read("served")
            resp = respond(out)
            resp.headers[REPLICA_EPOCH_HEADER] = str(epoch)
            resp.headers["X-HoraeDB-Replica-Lag-Ms"] = str(lag_ms)
            return resp

        if replica_read:
            # forwarded here as a replica read but we no longer serve
            # these tables (replica set changed under the route cache)
            note_replica_read("fenced")
            return web.json_response(
                {
                    "error": f"{proto} tables not replicated on this node",
                    "replica": "replica_fenced",
                },
                status=503,
            )
        if request.headers.get(FORWARD_HEADER):
            return None  # one hop only, like _forward_if_remote
        # offload: every target table routed to ONE remote leader whose
        # shard has follower replicas -> try a replica before the leader
        routes = {t: router.route(t) for t in set(tables)}
        if len({r.endpoint for r in routes.values()}) != 1:
            return None
        route0 = next(iter(routes.values()))
        if route0.is_local or not route0.replicas:
            return None
        pick = getattr(router, "pick_replica", None)
        target = (
            pick(route0, exclude=getattr(router, "self_endpoint", ""))
            if pick is not None
            else None
        )
        if target is None:
            return None
        import aiohttp

        from ..utils.deadline import Deadline

        body = await request.read()
        raw_budget = _parse_timeout_ms(request.headers.get(TIMEOUT_HEADER))
        if raw_budget is not None and raw_budget <= 0:
            # already expired on arrival: refuse like the /sql path
            from ..utils.deadline import note_expired

            note_expired("ingress")
            return web.json_response(
                {"error": "request arrived with an exhausted time budget"},
                status=504,
            )
        fwd_deadline = Deadline(raw_budget)
        headers = {
            FORWARD_HEADER: "1",
            REPLICA_READ_HEADER: "1",
            REPLICA_EPOCH_HEADER: str(route0.epoch),
            "Content-Type": request.headers.get(
                "Content-Type", "application/json"
            ),
            **_budget_headers(fwd_deadline),
        }
        if staleness_ms:
            headers[STALENESS_HEADER] = f"{int(staleness_ms)}ms"
        try:
            session = await _client_session(request.app)
            async with session.request(
                request.method,
                f"http://{target}{request.path_qs}",
                data=body,
                headers=headers,
                timeout=_forward_client_timeout(request.app, fwd_deadline),
            ) as resp:
                payload = await resp.read()
                if resp.status == 200:
                    out = web.Response(
                        body=payload,
                        status=200,
                        content_type=resp.content_type,
                    )
                    for h in (REPLICA_EPOCH_HEADER, "X-HoraeDB-Replica-Lag-Ms"):
                        if h in resp.headers:
                            out.headers[h] = resp.headers[h]
                    return out
        except (aiohttp.ClientError, asyncio.TimeoutError):
            pass  # follower unreachable: the leader still can
        # typed refusal or any other follower failure: fall back to the
        # normal path (leader forward / local evaluation)
        note_replica_read("stale_fallback")
        return None

    # ---- protocol front ends -------------------------------------------
    async def influx_write(request: web.Request) -> web.Response:
        from ..proxy.influxdb import LineProtocolError, parse_lines, write_points

        precision = request.query.get("precision", "ns")
        body = (await request.read()).decode("utf-8", "replace")

        def do():
            import time as _time

            points = parse_lines(body, precision)
            # Same limiter/quota/hotspot discipline as /sql and /write.
            measurements: dict[str, int] = {}
            for p in points:
                measurements[p.measurement] = measurements.get(p.measurement, 0) + 1
            for m in measurements:
                proxy.limiter.check(m)
            # one all-or-nothing debit: a rejected batch leaves the
            # tenant and every table bucket untouched, so retries of the
            # same payload don't drain unrelated allowances
            proxy.wlm.quota.charge_write_batch("default", measurements)
            n = write_points(conn.catalog, points, now_ms=int(_time.time() * 1000))
            for m in measurements:
                proxy.hotspot.record(m, True)
            return n

        try:
            n = await asyncio.get_running_loop().run_in_executor(None, do)
        except LineProtocolError as e:
            return web.json_response({"error": str(e)}, status=400)
        except BlockedError as e:
            return web.json_response({"error": str(e)}, status=403)
        except OverloadedError as e:
            return web.json_response(
                {"error": str(e)}, status=503,
                headers={"Retry-After": str(max(1, int(round(e.retry_after_s))))},
            )
        except QuotaExceededError as e:
            return web.json_response(
                {"error": str(e)}, status=429,
                headers={"Retry-After": str(max(1, int(round(e.retry_after_s))))},
            )
        except Exception as e:
            return web.json_response({"error": str(e)}, status=422)
        proxy.wlm.dedup.bump_epoch()
        # Influx v1 returns 204 No Content on success.
        return web.Response(status=204, headers={"X-Written-Rows": str(n)})

    async def influx_query(request: web.Request) -> web.Response:
        """InfluxDB v1 /query endpoint (ref: influxdb/mod.rs:52-61)."""
        from ..proxy.influxql import InfluxQLError, evaluate

        params = dict(request.query)
        if request.method == "POST":
            try:
                params.update(await request.post())
            except Exception:
                pass
        q = params.get("q", "")
        if not q:
            return web.json_response(
                {"error": "missing query parameter 'q'"}, status=400
            )
        if router is not None and cluster is not None:
            # Replicated follower reads (PR-10 remainder): a historical
            # statement (guaranteed upper time bound) serves from a
            # follower replica — locally when this node replicates the
            # measurements, else offloaded via pick_replica with leader
            # fallback — with route=follower in query_stats.
            from ..proxy.influxql import replica_read_targets

            targets = replica_read_targets(q)
            if targets is not None:
                resp = await _follower_protocol(
                    request, targets[0], targets[1], "influxql",
                    run_local=lambda: evaluate(conn, q),
                    respond=lambda data: web.Response(
                        text=_dumps(data), content_type="application/json"
                    ),
                )
                if resp is not None:
                    return resp
            elif request.headers.get(REPLICA_READ_HEADER):
                # forwarded as a replica read but not an eligible shape
                # here: typed refusal, the origin owns the fallback
                return web.json_response(
                    {"error": "influxql read not replica-servable",
                     "replica": "replica_stale"},
                    status=503,
                )
        try:
            proxy._m_queries.inc()
            data = await asyncio.get_running_loop().run_in_executor(
                None, evaluate, conn, q
            )
        except (InfluxQLError, ValueError) as e:
            proxy._m_errors.inc()
            return web.json_response({"error": str(e)}, status=400)
        except Exception as e:
            proxy._m_errors.inc()
            return web.json_response({"error": str(e)}, status=422)
        return web.Response(text=_dumps(data), content_type="application/json")

    async def opentsdb_query(request: web.Request) -> web.Response:
        """OpenTSDB /api/query (ref: opentsdb/mod.rs read side)."""
        from ..proxy.opentsdb import OpenTsdbError, evaluate_query

        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"error": "invalid JSON"}, status=400)
        if router is not None and cluster is not None:
            # historical query (explicit end bound) -> follower-eligible
            targets = None
            try:
                if (
                    isinstance(body, dict)
                    and body.get("end") is not None
                    and body.get("queries")
                ):
                    from ..proxy.opentsdb import _normalize_ts

                    targets = (
                        [str(sub["metric"]) for sub in body["queries"]],
                        _normalize_ts(body["end"]) + 1,  # inclusive end
                    )
            except Exception:
                targets = None
            if targets is not None:
                resp = await _follower_protocol(
                    request, targets[0], targets[1], "opentsdb",
                    run_local=lambda: evaluate_query(conn, body),
                    respond=lambda data: web.Response(
                        text=_dumps(data), content_type="application/json"
                    ),
                )
                if resp is not None:
                    return resp
            elif request.headers.get(REPLICA_READ_HEADER):
                return web.json_response(
                    {"error": "opentsdb read not replica-servable",
                     "replica": "replica_stale"},
                    status=503,
                )
        try:
            proxy._m_queries.inc()
            data = await asyncio.get_running_loop().run_in_executor(
                None, evaluate_query, conn, body
            )
        except OpenTsdbError as e:
            proxy._m_errors.inc()
            return web.json_response({"error": str(e)}, status=400)
        except Exception as e:
            proxy._m_errors.inc()
            return web.json_response({"error": str(e)}, status=422)
        return web.Response(text=_dumps(data), content_type="application/json")

    async def prom_remote_read(request: web.Request) -> web.Response:
        """Prometheus remote-read: snappy-framed protobuf over HTTP POST
        (ref: the reference's Prom remote query, grpc/prom_query.rs)."""
        from ..proxy.prom_remote import RemoteReadError, handle_remote_read

        raw = await request.read()
        try:
            proxy._m_queries.inc()
            payload = await asyncio.get_running_loop().run_in_executor(
                None, handle_remote_read, conn, raw
            )
        except RemoteReadError as e:
            proxy._m_errors.inc()
            return web.json_response({"error": str(e)}, status=400)
        except Exception as e:
            proxy._m_errors.inc()
            return web.json_response({"error": str(e)}, status=422)
        return web.Response(
            body=payload,
            content_type="application/x-protobuf",
            headers={"Content-Encoding": "snappy"},
        )

    async def opentsdb_put(request: web.Request) -> web.Response:
        from ..proxy.opentsdb import OpenTsdbError, parse_put, write_points as otsdb_write

        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"error": "invalid JSON"}, status=400)

        def do():
            points = parse_put(body)
            metrics_count: dict[str, int] = {}
            for p in points:
                metrics_count[p["metric"]] = metrics_count.get(p["metric"], 0) + 1
            for m in metrics_count:
                proxy.limiter.check(m)
            proxy.wlm.quota.charge_write_batch("default", metrics_count)
            n = otsdb_write(conn.catalog, points)
            for m in metrics_count:
                proxy.hotspot.record(m, True)
            return n

        try:
            await asyncio.get_running_loop().run_in_executor(None, do)
        except OpenTsdbError as e:
            return web.json_response({"error": str(e)}, status=400)
        except BlockedError as e:
            return web.json_response({"error": str(e)}, status=403)
        except OverloadedError as e:
            return web.json_response(
                {"error": str(e)}, status=503,
                headers={"Retry-After": str(max(1, int(round(e.retry_after_s))))},
            )
        except QuotaExceededError as e:
            return web.json_response(
                {"error": str(e)}, status=429,
                headers={"Retry-After": str(max(1, int(round(e.retry_after_s))))},
            )
        except Exception as e:
            return web.json_response({"error": str(e)}, status=422)
        proxy.wlm.dedup.bump_epoch()
        return web.Response(status=204)

    async def prom_query(request: web.Request) -> web.Response:
        """Prometheus HTTP API subset (ref: /prom/v1/* routes, http.rs).

        /prom/v1/query_range: query, start, end (unix seconds), step
        /prom/v1/query:       query, time (unix seconds)
        """
        from ..proxy.promql import (
            PromQLError,
            evaluate_expr_instant,
            evaluate_expr_range,
            leaf_metrics,
            parse_promql,
        )

        params = dict(request.query)
        if request.method == "POST":
            params.update(await request.post())
        q = params.get("query", "")
        if not q:
            return web.json_response(
                {"status": "error", "error": "missing 'query'"}, status=400
            )
        is_range = request.path.endswith("query_range")
        try:
            pq = parse_promql(q)
        except PromQLError as e:
            return web.json_response({"status": "error", "error": str(e)}, status=400)
        # Same routing + limiter/hotspot/metrics discipline as /sql.
        # Expressions route on their leaf metrics: forwarding applies when
        # every leaf lives on the same (remote) node; mixed-owner
        # expressions evaluate here over the forwarding SQL layer.
        def _prom_route_key(m: str) -> str:
            # Self-monitoring fallback: a metric with no table of its
            # own evaluates against system_metrics.samples — route on
            # where THAT lives, using the same predicate evaluation
            # applies so routing and evaluation cannot disagree.
            from ..engine.metrics_recorder import SAMPLES_TABLE
            from ..proxy.promql import resolves_to_samples

            if resolves_to_samples(conn, m):
                return SAMPLES_TABLE
            return m

        def run():
            if is_range:
                for p in ("start", "end"):
                    if p not in params:
                        raise PromQLError(f"missing parameter {p!r}")
                start = int(float(params["start"]) * 1000)
                end = int(float(params["end"]) * 1000)
                step_raw = params.get("step", "60")
                from ..engine.options import parse_duration_ms

                step = (
                    parse_duration_ms(step_raw)
                    if not step_raw.replace(".", "").isdigit()
                    else int(float(step_raw) * 1000)
                )
                if step <= 0:
                    raise PromQLError("step must be positive")
                result = evaluate_expr_range(conn, pq, start, end, step)
                return {"resultType": "matrix", "result": result}
            import time as _time

            # Prometheus defaults the evaluation time to "now".
            t = int(float(params.get("time", _time.time())) * 1000)
            result = evaluate_expr_instant(conn, pq, t)
            return {"resultType": "vector", "result": result}

        metrics = leaf_metrics(pq)
        if router is not None and cluster is not None and metrics:
            # Replicated follower reads (PR-10 remainder): route the
            # evaluation through a follower replica of the leaf tables —
            # locally when this node replicates them all, else offloaded
            # via pick_replica with leader fallback. The evaluation end
            # (explicit or "now") is the bound the follower's watermark
            # (or a staleness opt-in) must cover.
            import time as _time

            end_raw = params.get("end") if is_range else params.get("time")
            try:
                prom_end_ms = (
                    int(float(end_raw) * 1000) + 1
                    if end_raw is not None
                    else int(_time.time() * 1000) + 1
                )
            except (TypeError, ValueError):
                end_raw = None
                prom_end_ms = int(_time.time() * 1000) + 1
            # an implicit "now" evaluation (the Grafana default) is never
            # watermark-covered: engaging the follower path would pay an
            # opportunistic manifest refresh per query just to fall back
            # to the leader. Only an EXPLICIT end/time, a staleness
            # opt-in, or a forwarded replica read (the origin owns the
            # fallback) makes the attempt worthwhile.
            eligible = (
                end_raw is not None
                or bool(_parse_staleness(request.headers.get(STALENESS_HEADER)))
                or bool(request.app.get("read_staleness_ms"))
                or bool(request.headers.get(REPLICA_READ_HEADER))
            )
            def run_checked():
                # follower serving must keep the same gate the normal
                # path applies: a blocked table is refused (the generic
                # failure mapping bounces a non-forwarded request to the
                # normal path, which raises the 403; a forwarded replica
                # read falls back to the leader, which enforces it)
                for m in set(metrics):
                    proxy.limiter.check(m)
                    proxy.hotspot.record(m, False)
                proxy._m_queries.inc()
                return run()

            if eligible:
                resp = await _follower_protocol(
                    request,
                    sorted({_prom_route_key(m) for m in metrics}),
                    prom_end_ms,
                    "promql",
                    run_local=run_checked,
                    respond=lambda data: web.Response(
                        text=_dumps({"status": "success", "data": data}),
                        content_type="application/json",
                    ),
                )
                if resp is not None:
                    return resp
        if len({_prom_route_key(m) for m in metrics}) == 1:
            forwarded = await _forward_if_remote(
                request, _prom_route_key(metrics[0])
            )
            if forwarded is not None:
                return forwarded
        elif router is not None and any(
            not router.route(_prom_route_key(m)).is_local for m in set(metrics)
        ):
            # A multi-metric expression whose leaves live on different
            # nodes would need a cross-node vector join — evaluating it
            # locally would silently produce empty/partial series, so
            # refuse loudly instead.
            return web.json_response(
                {
                    "status": "error",
                    "error": "expression spans tables owned by other nodes; "
                    "query it against the owning node",
                },
                status=400,
            )
        try:
            proxy._m_queries.inc()
            for m in set(metrics):
                proxy.limiter.check(m)
                proxy.hotspot.record(m, False)
            data = await asyncio.get_running_loop().run_in_executor(None, run)
        except BlockedError as e:
            proxy._m_errors.inc()
            return web.json_response({"status": "error", "error": str(e)}, status=403)
        except (PromQLError, KeyError, ValueError) as e:
            proxy._m_errors.inc()
            return web.json_response(
                {"status": "error", "error": str(e)}, status=400
            )
        except Exception as e:
            proxy._m_errors.inc()
            return web.json_response(
                {"status": "error", "error": str(e)}, status=422
            )
        return web.Response(
            text=_dumps({"status": "success", "data": data}),
            content_type="application/json",
        )

    # ---- observability -------------------------------------------------
    async def metrics(request: web.Request) -> web.Response:
        # Prometheus exposition content type (version param included —
        # some scrapers refuse bare text/plain).
        return web.Response(
            body=REGISTRY.expose().encode("utf-8"),
            headers={
                "Content-Type": "text/plain; version=0.0.4; charset=utf-8"
            },
        )

    def _node_ready() -> bool:
        """Ready = the engine can serve: startup warmup finished (lazy
        table opens would otherwise report replay 'done' before it ever
        started), no WAL replay in flight, not closed, rule state loaded
        (a node serving before its runtime rules/watermarks load would
        evaluate a stale rule set and re-derive rollup watermarks cold)
        — and in cluster mode at least one shard opened (a node with
        zero shards serves reads/forwards but isn't "ready" as a write
        target yet). Cheap on purpose: probes fire every few seconds."""
        if not app["warmup_done"] or not conn.instance.is_ready():
            return False
        eng = app["rule_engine"]
        if eng is not None and not eng.loaded:
            return False
        return cluster is None or bool(cluster.debug_shard_info())

    def _node_status() -> dict:
        """One JSON document an operator (or k8s probe) reads first:
        uptime, identity, shard set, WAL-replay progress, background
        scheduler queue/backoff state, memtable pressure, admission
        slots, and the self-monitoring recorder's state."""
        import time as _time

        engine = conn.instance.status()
        adm = proxy.wlm.admission.snapshot()
        shards = cluster.debug_shard_info() if cluster is not None else []
        ready = _node_ready()
        rec = app["metrics_recorder"]
        return {
            "status": "ok",
            "ready": ready,
            "uptime_s": round(_time.time() - app["started_at"], 3),
            "node": app["node"],
            "role": "cluster" if cluster is not None else (
                "static-cluster" if router is not None else "standalone"
            ),
            "shard_count": len(shards),
            "engine": engine,
            "admission": {
                "units_in_use": adm["units_in_use"],
                "total_units": adm["total_units"],
                "queue_depth": adm["queue_depth"],
            },
            "self_monitoring": rec.stats() if rec is not None else None,
            "rules": (
                app["rule_engine"].stats()
                if app["rule_engine"] is not None
                else None
            ),
            "slo": (
                app["slo"].stats() if app["slo"] is not None else None
            ),
            # journal bounds: a reader of system.public.events needs the
            # drop count to tell "ring rolled" from "events lost"
            "events": _event_store_stats(),
        }

    async def health(request: web.Request) -> web.Response:
        """Liveness by default; ``?ready=1`` adds the readiness gate a
        k8s readinessProbe wants: 503 until WAL replay finished (and, in
        cluster mode, at least one shard opened)."""
        if not _query_flag(request, "ready"):
            return web.json_response({"status": "ok"})
        ready = await asyncio.get_running_loop().run_in_executor(
            None, _node_ready
        )
        body = {"status": "ok" if ready else "not_ready", "ready": ready}
        return web.json_response(body, status=200 if ready else 503)

    def _event_store_stats() -> dict:
        from ..utils.events import EVENT_STORE

        return EVENT_STORE.stats()

    async def debug_status(request: web.Request) -> web.Response:
        out = await asyncio.get_running_loop().run_in_executor(
            None, _node_status
        )
        return web.Response(text=_dumps(out), content_type="application/json")

    async def debug_slo(request: web.Request) -> web.Response:
        """The SLO plane's verdicts — the JSON face of
        ``system.public.slo`` (per-objective state, current value, fast/
        slow burn rates, breach history)."""
        ev = request.app["slo"]
        if ev is None:
            return web.json_response(
                {"enabled": False, "objectives": [], "breaches": []}
            )

        def collect():
            # off the event loop: snapshot() takes the evaluator lock,
            # which an in-flight evaluation round briefly holds
            return {
                "enabled": True,
                "objectives": ev.snapshot(),
                "breaches": ev.breach_history(),
                "stats": ev.stats(),
            }

        out = await asyncio.get_running_loop().run_in_executor(None, collect)
        return web.Response(
            text=_dumps(out), content_type="application/json",
        )

    async def debug_events(request: web.Request) -> web.Response:
        """The engine event journal (utils/events): newest-bounded ring
        of typed lifecycle events, each carrying the trace_id of the
        request that caused it. ?kind= filters, ?limit= tails."""
        from ..utils.events import EVENT_STORE

        kind = request.query.get("kind")
        limit = None
        if "limit" in request.query:
            try:
                limit = int(request.query["limit"])
            except ValueError:
                return web.json_response({"error": "bad 'limit'"}, status=400)
        return web.Response(
            text=_dumps({"events": EVENT_STORE.list(kind=kind, limit=limit)}),
            content_type="application/json",
        )

    async def debug_decisions(request: web.Request) -> web.Response:
        """The decision plane (obs/decisions): the journal's newest-
        bounded ring plus per-loop calibration and the accounting
        ledger. ?loop= filters, ?limit= tails — filter parity with
        /debug/events."""
        from ..obs.decisions import DECISION_JOURNAL, DECISION_LOOPS

        loop = request.query.get("loop")
        if loop is not None and loop not in DECISION_LOOPS:
            return web.json_response(
                {"error": f"unknown loop {loop!r} "
                          f"(one of {', '.join(DECISION_LOOPS)})"},
                status=400,
            )
        limit = None
        if "limit" in request.query:
            try:
                limit = int(request.query["limit"])
            except ValueError:
                return web.json_response({"error": "bad 'limit'"}, status=400)
        return web.Response(
            text=_dumps(
                {
                    "decisions": DECISION_JOURNAL.list(loop=loop, limit=limit),
                    "calibration": DECISION_JOURNAL.calibration(),
                    "stats": DECISION_JOURNAL.stats(),
                }
            ),
            content_type="application/json",
        )

    async def debug_profile(request: web.Request) -> web.Response:
        """The continuous profile plane (obs/profile): live (path,
        route, shape) rows exclusive-heavy first, plus the aggregator's
        fleetwide accounting header. ?path= filters by prefix, ?route=
        by plane, ?limit= caps rows — filter parity with
        /debug/decisions."""
        from ..obs.profile import PROFILE

        path = request.query.get("path")
        route_q = request.query.get("route")
        limit = 0
        if "limit" in request.query:
            try:
                limit = int(request.query["limit"])
            except ValueError:
                return web.json_response({"error": "bad 'limit'"}, status=400)
        return web.Response(
            text=_dumps(
                {
                    "profile": PROFILE.list(
                        path=path, route=route_q, limit=limit
                    ),
                    "stats": PROFILE.stats(),
                }
            ),
            content_type="application/json",
        )

    async def route(request: web.Request) -> web.Response:
        """One payload shape in both modes:
        routes[i] = {endpoint, is_local, shard_id|null}."""
        table = request.match_info["table"]
        if router is not None:
            r = router.route(table)
            return web.json_response(
                {
                    "table": table,
                    "routes": [
                        {"endpoint": r.endpoint, "is_local": r.is_local, "shard_id": None}
                    ],
                }
            )
        if not conn.catalog.exists(table):
            return web.json_response({"error": f"table not found: {table}"}, status=404)
        # Standalone: this node owns everything.
        return web.json_response(
            {
                "table": table,
                "routes": [{"endpoint": "local", "is_local": True, "shard_id": 0}],
            }
        )

    async def debug_config(request: web.Request) -> web.Response:
        inst = conn.instance
        return web.json_response(
            {
                "engine": {
                    "space_write_buffer_size": inst.config.space_write_buffer_size,
                    "compaction_l0_trigger": inst.config.compaction_l0_trigger,
                    "compaction_workers": inst.config.compaction_workers,
                    "background_flush": inst.config.background_flush,
                    "flush_workers": inst.config.flush_workers,
                    "write_stall_immutable_count":
                        inst.config.write_stall_immutable_count,
                    "write_stall_immutable_bytes":
                        inst.config.write_stall_immutable_bytes,
                    "write_stall_deadline_s":
                        inst.config.write_stall_deadline_s,
                    "wal": type(inst.wal).__name__ if inst.wal else None,
                },
                "slow_threshold_s": proxy.slow_threshold_s,
            }
        )

    async def debug_tables(request: web.Request) -> web.Response:
        def collect():
            # open_table may do manifest load + WAL replay for cold tables:
            # real blocking IO, so this runs off the event loop.
            out = {}
            for name in conn.catalog.table_names():
                try:
                    t = conn.catalog.open(name)
                except Exception as e:
                    out[name] = {"error": str(e)}
                    continue
                if t is not None:
                    out[name] = t.metrics()
            return out

        out = await asyncio.get_running_loop().run_in_executor(None, collect)
        return web.Response(text=_dumps(out), content_type="application/json")

    async def debug_hotspot(request: web.Request) -> web.Response:
        return web.json_response(proxy.hotspot.top())

    async def debug_queries(request: web.Request) -> web.Response:
        """Recent per-query metric trees (ref: trace_metric surfaces).
        ``?live=1`` returns the LIVE in-flight registry instead (the
        same rows as ``system.public.queries``; DELETE
        /debug/queries/{id} kills one)."""
        if _query_flag(request, "live"):
            from ..utils.deadline import QUERY_REGISTRY

            return web.Response(
                text=_dumps(QUERY_REGISTRY.list()),
                content_type="application/json",
            )
        return web.Response(
            text=_dumps(list(proxy.recent_queries)), content_type="application/json"
        )

    async def debug_query_kill(request: web.Request) -> web.Response:
        """Cooperative kill: flips the cancel flag on a live query; the
        executor observes it at its next checkpoint and unwinds with the
        typed QueryCancelled (admission slot, dedup flight and cohort
        membership all released on the way out)."""
        from ..utils.deadline import QUERY_REGISTRY

        raw = request.match_info["query_id"]
        if not raw.isdigit():
            return web.json_response({"error": "bad query id"}, status=400)
        if not QUERY_REGISTRY.kill(int(raw), source="kill"):
            return web.json_response(
                {"error": f"no live query {raw}"}, status=404
            )
        return web.json_response({"killed": int(raw)})

    async def slow_threshold(request: web.Request) -> web.Response:
        try:
            proxy.slow_threshold_s = float(request.match_info["seconds"])
        except ValueError:
            return web.json_response({"error": "bad threshold"}, status=400)
        return web.json_response({"slow_threshold_s": proxy.slow_threshold_s})

    async def debug_profile_cpu(request: web.Request) -> web.Response:
        """Sampling CPU profile (ref: /debug/profile/cpu/{sec}, http.rs:539)."""
        from ..utils.profile import sample_cpu

        try:
            seconds = float(request.match_info["seconds"])
        except ValueError:
            seconds = float("nan")
        if not (0.0 <= seconds <= 60.0):  # also rejects NaN
            return web.json_response({"error": "bad duration"}, status=400)
        text = await asyncio.get_running_loop().run_in_executor(
            None, sample_cpu, seconds
        )
        return web.Response(text=text, content_type="text/plain")

    async def debug_profile_heap(request: web.Request) -> web.Response:
        """tracemalloc growth profile (ref: /debug/profile/heap/{sec})."""
        from ..utils.profile import sample_heap

        try:
            seconds = float(request.match_info["seconds"])
        except ValueError:
            seconds = float("nan")
        if not (0.0 <= seconds <= 60.0):  # also rejects NaN
            return web.json_response({"error": "bad duration"}, status=400)
        text = await asyncio.get_running_loop().run_in_executor(
            None, sample_heap, seconds
        )
        return web.Response(text=text, content_type="text/plain")

    async def debug_log_level(request: web.Request) -> web.Response:
        """Live log-level switch (ref: /debug/log_level/{level}, http.rs:643
        + the RuntimeLevel in components/logger)."""
        level = request.match_info["level"].upper()
        if level not in ("DEBUG", "INFO", "WARNING", "WARN", "ERROR", "CRITICAL"):
            return web.json_response({"error": f"unknown level {level!r}"}, status=400)
        logging.getLogger().setLevel("WARNING" if level == "WARN" else level)
        return web.json_response({"log_level": level})

    async def debug_shards(request: web.Request) -> web.Response:
        """This node's shard set (ref: /debug/shards, http.rs:587)."""
        if cluster is None:
            return web.json_response({"mode": "standalone", "shards": []})
        return web.json_response(
            {
                "mode": "cluster",
                "endpoint": cluster.self_endpoint,
                "shards": cluster.debug_shard_info(),
            }
        )

    async def debug_wal_stats(request: web.Request) -> web.Response:
        """WAL backend introspection (ref: /debug/wal_stats, http.rs:587)."""
        wal = conn.instance.wal
        if wal is None:
            return web.json_response({"backend": None})
        out = await asyncio.get_running_loop().run_in_executor(None, wal.stats)
        return web.json_response(out)

    async def debug_compaction(request: web.Request) -> web.Response:
        """Background compaction scheduler state: queue, in-flight count,
        per-table failure backoff (ref model: the reference scheduler's
        ScheduleRoom/token visibility through its admin surface)."""
        return web.json_response(conn.instance.compaction_stats())

    async def debug_flush(request: web.Request) -> web.Response:
        """Background flush scheduler state (same shape as
        /debug/compaction): queue, in-flight dumps, per-table failure
        backoff — the pipelined-flush half of the maintenance surface."""
        return web.json_response(conn.instance.flush_stats())

    async def debug_slow_log(request: web.Request) -> web.Response:
        """Recent slow queries (ref: the reference's slow-query log file)."""
        return web.Response(
            text=_dumps(list(proxy.slow_queries)), content_type="application/json"
        )

    async def debug_query_stats(request: web.Request) -> web.Response:
        """Recent finalized per-query cost ledgers — the same rows the
        SQL-queryable ``system.public.query_stats`` table serves."""
        from ..utils.querystats import STATS_STORE

        return web.Response(
            text=_dumps({"queries": STATS_STORE.list()}),
            content_type="application/json",
        )

    async def debug_trace_list(request: web.Request) -> web.Response:
        """Recent + slow trace summaries from the bounded in-process
        store (ref: trace_metric's collector surfaces)."""
        from ..utils.tracectx import TRACE_STORE

        return web.Response(
            text=_dumps({"traces": TRACE_STORE.list()}),
            content_type="application/json",
        )

    async def debug_trace_get(request: web.Request) -> web.Response:
        """Full span tree of one request, by its request/trace id."""
        from ..utils.tracectx import TRACE_STORE

        raw = request.match_info["request_id"]
        try:
            key = int(raw)
        except ValueError:
            key = raw
        entry = TRACE_STORE.get(key)
        if entry is None:
            return web.json_response(
                {"error": f"no trace for request id {raw!r}"}, status=404
            )
        return web.Response(text=_dumps(entry), content_type="application/json")

    async def debug_remote_spans(request: web.Request) -> web.Response:
        """Remote partial-agg spans served BY this node, keyed by the
        origin coordinator's request id (ref: RemoteTaskContext
        .remote_metrics carrying EXPLAIN ANALYZE data across nodes)."""
        with conn.remote_spans_lock:
            spans = list(conn.remote_spans)
        return web.json_response({"spans": spans})

    async def admin_flush(request: web.Request) -> web.Response:
        """Force a flush (all tables, or ?table=name)."""
        name = request.query.get("table")

        def do():
            if name:
                t = conn.catalog.open(name)
                if t is None:
                    raise ValueError(f"table not found: {name}")
                t.flush()
                return [name]
            conn.flush_all()
            return conn.catalog.table_names()

        try:
            flushed = await asyncio.get_running_loop().run_in_executor(None, do)
        except Exception as e:
            return web.json_response({"error": str(e)}, status=422)
        return web.json_response({"flushed": flushed})

    async def admin_block(request: web.Request) -> web.Response:
        try:
            tables = (await request.json())["tables"]
        except Exception:
            tables = None
        if not isinstance(tables, list) or not all(isinstance(t, str) for t in tables):
            return web.json_response(
                {"error": "body must be {'tables': ['name', ...]}"}, status=400
            )
        if request.method == "POST":
            proxy.limiter.block(tables)
        else:
            proxy.limiter.unblock(tables)
        # block/unblock persist through the quota manager's state file —
        # a restarted node comes back with the operator's limits applied
        return web.json_response({"blocked": proxy.limiter.blocked()})

    async def debug_workload(request: web.Request) -> web.Response:
        """Live workload-manager state: admission slots/queues, dedup
        flights, quota buckets — the same state served SQL-side by
        ``system.public.workload``."""
        return web.Response(
            text=_dumps(proxy.wlm.snapshot()), content_type="application/json"
        )

    async def debug_device(request: web.Request) -> web.Response:
        """The device telemetry plane (obs/device): HBM residency
        inventory (the same rows served SQL-side by
        ``system.public.device``), byte totals by component, per-kernel
        compile-cache stats."""
        from ..obs import device as obs_device

        def collect():
            rows = obs_device.device_inventory()
            return {
                "enabled": obs_device.device_telemetry_enabled(),
                "inventory": rows,
                "totals": obs_device.occupancy_totals(rows),
                "compile": obs_device.compile_stats(),
            }

        out = await asyncio.get_running_loop().run_in_executor(None, collect)
        return web.Response(text=_dumps(out), content_type="application/json")

    async def debug_livewindow(request: web.Request) -> web.Response:
        """Live window state plane (state/livewindow): resident ring
        states (window, groups, bytes, head bucket, dirty counts, reads
        served), shapes pending promotion, and the byte budget in
        force. DELETE /debug/livewindow/{key} evicts one state."""
        from ..state.livewindow import STORE

        key = request.match_info.get("key")
        if request.method == "DELETE":
            if STORE.get(key) is None:
                raise web.HTTPNotFound(text=f"no live window state {key!r}")
            STORE.drop(key, outcome="evict")
            return web.Response(
                text=_dumps({"evicted": key}), content_type="application/json"
            )
        out = await asyncio.get_running_loop().run_in_executor(None, STORE.stats)
        return web.Response(text=_dumps(out), content_type="application/json")

    async def admin_quota(request: web.Request) -> web.Response:
        """GET: current quotas + block-list. POST: set a token bucket
        {"scope": "table"|"tenant", "name": ..., "kind":
        "read_qps"|"write_rows", "rate": r, "burst"?: b}. DELETE: remove
        one. State persists across restarts via the config layer."""
        if request.method == "GET":
            return web.Response(
                text=_dumps(proxy.wlm.quota.snapshot()),
                content_type="application/json",
            )
        try:
            body = await request.json()
            scope = body["scope"]
            name = body["name"]
            kind = body["kind"]
        except Exception:
            return web.json_response(
                {"error": "body must be {'scope', 'name', 'kind', ...}"},
                status=400,
            )
        if request.method == "DELETE":
            removed = proxy.wlm.quota.remove_quota(scope, name, kind)
            return web.json_response(
                {"removed": removed, **proxy.wlm.quota.snapshot()}
            )
        try:
            rate = float(body["rate"])
            burst = body.get("burst")
            proxy.wlm.quota.set_quota(
                scope, name, kind, rate,
                float(burst) if burst is not None else None,
            )
        except (KeyError, TypeError, ValueError) as e:
            return web.json_response({"error": str(e)}, status=400)
        return web.Response(
            text=_dumps(proxy.wlm.quota.snapshot()),
            content_type="application/json",
        )

    async def debug_alerts(request: web.Request) -> web.Response:
        """The rule engine's alert state — the JSON face of
        ``system.public.alerts`` (pending/firing live instances plus the
        recently-resolved ring)."""
        eng = request.app["rule_engine"]
        if eng is None:
            return web.json_response({"enabled": False, "alerts": []})
        return web.Response(
            text=_dumps({"enabled": True, "alerts": eng.alerts_snapshot()}),
            content_type="application/json",
        )

    async def admin_rules(request: web.Request) -> web.Response:
        """GET: loaded rules (config + runtime) with last errors.
        POST: add a runtime rule {"kind": "recording"|"alert", "name":
        ..., "expr": ..., "for"?: "30s", "labels"?: {...}} — validated
        and persisted beside wlm_state.json. DELETE: {"name": ...}
        removes a runtime rule (config rules refuse)."""
        from ..rules import RuleError

        eng = request.app["rule_engine"]
        if eng is None:
            return web.json_response(
                {"error": "rules engine disabled on this node"}, status=400
            )
        if request.method == "GET":
            return web.Response(
                text=_dumps({"rules": eng.list_rules(),
                             "rollup_tables": list(eng.rollup_sources)}),
                content_type="application/json",
            )
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"error": "invalid JSON body"}, status=400)
        loop = asyncio.get_running_loop()
        if request.method == "DELETE":
            name = body.get("name") if isinstance(body, dict) else None
            if not isinstance(name, str) or not name:
                return web.json_response(
                    {"error": "body must be {'name': ...}"}, status=400
                )
            try:
                removed = await loop.run_in_executor(
                    None, eng.remove_rule, name
                )
            except RuleError as e:
                return web.json_response({"error": str(e)}, status=400)
            return web.json_response(
                {"removed": removed, "rules": eng.list_rules()}
            )
        try:
            rule = await loop.run_in_executor(None, eng.add_rule, body)
        except RuleError as e:
            return web.json_response({"error": str(e)}, status=400)
        return web.json_response({"added": rule.to_dict()})

    # ---- meta events (coordinator -> data node; ref: MetaEventService,
    # grpc/meta_event_service/mod.rs:638-696) ----------------------------
    async def meta_open_shard(request: web.Request) -> web.Response:
        if cluster is None:
            return web.json_response({"error": "not in cluster mode"}, status=400)
        order = await request.json()
        try:
            await asyncio.get_running_loop().run_in_executor(
                None, cluster.apply_shard_order, order
            )
        except Exception as e:
            return web.json_response({"error": str(e)}, status=422)
        # Push orders carry no lease (they could be arbitrarily stale —
        # see apply_shard_order); fetch one via an immediate heartbeat so
        # the shard is writable in milliseconds, not a renewal later.
        cluster.kick_heartbeat()
        return web.json_response({"ok": True})

    async def meta_close_shard(request: web.Request) -> web.Response:
        if cluster is None:
            return web.json_response({"error": "not in cluster mode"}, status=400)
        body = await request.json()
        try:
            await asyncio.get_running_loop().run_in_executor(
                None, cluster.close_shard, int(body["shard_id"]), body.get("version")
            )
        except Exception as e:
            return web.json_response({"error": str(e)}, status=422)
        return web.json_response({"ok": True})

    async def meta_create_table(request: web.Request) -> web.Response:
        if cluster is None:
            return web.json_response({"error": "not in cluster mode"}, status=400)
        body = await request.json()
        try:
            out = await asyncio.get_running_loop().run_in_executor(
                None,
                cluster.create_table_on_shard,
                int(body["shard_id"]),
                body["name"],
                body["create_sql"],
            )
        except Exception as e:
            return web.json_response({"error": str(e)}, status=422)
        return web.json_response(out)

    async def meta_drop_table(request: web.Request) -> web.Response:
        if cluster is None:
            return web.json_response({"error": "not in cluster mode"}, status=400)
        body = await request.json()
        try:
            await asyncio.get_running_loop().run_in_executor(
                None, cluster.drop_table_on_shard, int(body["shard_id"]), body["name"]
            )
        except Exception as e:
            return web.json_response({"error": str(e)}, status=422)
        return web.json_response({"ok": True})

    async def meta_open_replica(request: web.Request) -> web.Response:
        if cluster is None:
            return web.json_response({"error": "not in cluster mode"}, status=400)
        order = await request.json()
        try:
            await asyncio.get_running_loop().run_in_executor(
                None, cluster.apply_replica_order, order
            )
        except Exception as e:
            return web.json_response({"error": str(e)}, status=422)
        # Like open_shard pushes: the replica lease arrives via the
        # kicked heartbeat, not the (possibly stale) push itself.
        cluster.kick_heartbeat()
        return web.json_response({"ok": True})

    app.router.add_post("/meta_event/open_shard", meta_open_shard)
    app.router.add_post("/meta_event/open_replica", meta_open_replica)
    app.router.add_post("/meta_event/close_shard", meta_close_shard)
    app.router.add_post("/meta_event/create_table_on_shard", meta_create_table)
    app.router.add_post("/meta_event/drop_table_on_shard", meta_drop_table)

    app.router.add_post("/sql", sql)
    app.router.add_post("/write", write)
    app.router.add_post("/influxdb/v1/write", influx_write)
    app.router.add_get("/influxdb/v1/query", influx_query)
    app.router.add_post("/influxdb/v1/query", influx_query)
    async def opentsdb_suggest(request: web.Request) -> web.Response:
        """OpenTSDB /api/suggest — metric/tagk/tagv autocomplete."""
        from ..proxy.opentsdb import OpenTsdbError, suggest

        kind = request.query.get("type", "metrics")
        q = request.query.get("q", "")
        try:
            mx = min(int(request.query.get("max", "25")), 1000)
        except ValueError:
            return web.json_response({"error": "bad 'max'"}, status=400)
        conn_ = request.app["conn"]
        try:
            out = await asyncio.get_running_loop().run_in_executor(
                None, suggest, conn_, kind, q, mx
            )
        except OpenTsdbError as e:
            return web.json_response({"error": str(e)}, status=400)
        return web.json_response(out)

    async def opentsdb_lookup(request: web.Request) -> web.Response:
        """OpenTSDB /api/search/lookup — enumerate a metric's series."""
        from ..proxy.opentsdb import OpenTsdbError, lookup

        try:
            if request.method == "POST":
                try:
                    body = await request.json()
                except ValueError:
                    return web.json_response({"error": "invalid JSON"}, status=400)
                metric = body.get("metric")
                tag_filters = body.get("tags") or []
                limit = int(body.get("limit", 25))
            else:
                # GET ?m=metric{k=v,k2=*}
                m = request.query.get("m", "")
                metric, _, tagspec = m.partition("{")
                tag_filters = []
                if tagspec:
                    if not tagspec.endswith("}"):
                        return web.json_response(
                            {"error": f"malformed tag spec in m={m!r}"},
                            status=400,
                        )
                    for pair in filter(None, tagspec[:-1].split(",")):
                        k, _, v = pair.partition("=")
                        tag_filters.append({"key": k.strip(), "value": v.strip()})
                limit = int(request.query.get("limit", "25"))
        except (TypeError, ValueError):
            return web.json_response({"error": "bad 'limit'"}, status=400)
        if not metric:
            return web.json_response({"error": "missing metric"}, status=400)
        if router is not None and not router.route(metric).is_local:
            # forward in canonical POST form — the raw-body forwarder
            # would POST a GET's empty body and lose the query string
            route = router.route(metric)
            if request.headers.get(FORWARD_HEADER):
                return web.json_response(
                    {"error": f"routing loop for {metric!r}"}, status=502
                )
            import aiohttp

            try:
                session = await _client_session(request.app)
                async with session.post(
                    f"http://{route.endpoint}/opentsdb/api/search/lookup",
                    json={"metric": metric, "tags": tag_filters, "limit": limit},
                    headers={FORWARD_HEADER: "1"},
                    timeout=_forward_client_timeout(request.app),
                ) as resp:
                    return web.json_response(
                        await resp.json(content_type=None), status=resp.status
                    )
            except (aiohttp.ClientError, asyncio.TimeoutError, ValueError) as e:
                return web.json_response(
                    {"error": f"forward to {route.endpoint} failed: {e}"},
                    status=502,
                )
        conn_ = request.app["conn"]
        try:
            out = await asyncio.get_running_loop().run_in_executor(
                None, lookup, conn_, metric, tag_filters, limit
            )
        except OpenTsdbError as e:
            return web.json_response({"error": str(e)}, status=400)
        return web.json_response(out)

    app.router.add_post("/opentsdb/api/put", opentsdb_put)
    app.router.add_post("/opentsdb/api/query", opentsdb_query)
    app.router.add_get("/opentsdb/api/suggest", opentsdb_suggest)
    app.router.add_get("/opentsdb/api/search/lookup", opentsdb_lookup)
    app.router.add_post("/opentsdb/api/search/lookup", opentsdb_lookup)
    app.router.add_post("/prom/v1/read", prom_remote_read)
    app.router.add_post("/api/v1/read", prom_remote_read)
    app.router.add_get("/prom/v1/query_range", prom_query)
    app.router.add_post("/prom/v1/query_range", prom_query)
    app.router.add_get("/prom/v1/query", prom_query)
    app.router.add_post("/prom/v1/query", prom_query)
    app.router.add_get("/metrics", metrics)
    app.router.add_get("/health", health)
    app.router.add_get("/route/{table}", route)
    app.router.add_get("/debug/config", debug_config)
    app.router.add_get("/debug/status", debug_status)
    app.router.add_get("/debug/events", debug_events)
    app.router.add_get("/debug/decisions", debug_decisions)
    app.router.add_get("/debug/tables", debug_tables)
    app.router.add_get("/debug/hotspot", debug_hotspot)
    app.router.add_get("/debug/queries", debug_queries)
    app.router.add_delete("/debug/queries/{query_id}", debug_query_kill)
    app.router.add_put("/debug/slow_threshold/{seconds}", slow_threshold)
    app.router.add_get("/debug/profile", debug_profile)
    app.router.add_get("/debug/profile/cpu/{seconds}", debug_profile_cpu)
    app.router.add_get("/debug/profile/heap/{seconds}", debug_profile_heap)
    app.router.add_put("/debug/log_level/{level}", debug_log_level)
    app.router.add_get("/debug/slow_log", debug_slow_log)
    app.router.add_get("/debug/query_stats", debug_query_stats)
    app.router.add_get("/debug/trace", debug_trace_list)
    app.router.add_get("/debug/trace/{request_id}", debug_trace_get)
    app.router.add_get("/debug/shards", debug_shards)
    app.router.add_get("/debug/wal_stats", debug_wal_stats)
    app.router.add_get("/debug/compaction", debug_compaction)
    app.router.add_get("/debug/flush", debug_flush)
    app.router.add_get("/debug/remote_spans", debug_remote_spans)
    app.router.add_get("/debug/workload", debug_workload)
    app.router.add_get("/debug/device", debug_device)
    app.router.add_get("/debug/livewindow", debug_livewindow)
    app.router.add_delete("/debug/livewindow/{key}", debug_livewindow)
    app.router.add_get("/debug/alerts", debug_alerts)
    app.router.add_get("/debug/slo", debug_slo)
    app.router.add_post("/admin/flush", admin_flush)
    app.router.add_post("/admin/block", admin_block)
    app.router.add_delete("/admin/block", admin_block)
    app.router.add_get("/admin/quota", admin_quota)
    app.router.add_post("/admin/quota", admin_quota)
    app.router.add_delete("/admin/quota", admin_quota)
    app.router.add_get("/admin/rules", admin_rules)
    app.router.add_post("/admin/rules", admin_rules)
    app.router.add_delete("/admin/rules", admin_rules)
    return app


def run_server(
    data_dir: Optional[str] = None,
    host: Optional[str] = None,
    port: Optional[int] = None,
    config=None,
) -> None:
    """One precedence rule: an explicit argument wins over ``config``,
    which wins over the defaults. (The CLI resolves its flags into the
    config before calling; programmatic callers can pass either form.)"""
    from ..engine.instance import EngineConfig

    engine_cfg = None
    slow_threshold = 1.0
    explicit_data_dir = data_dir  # before config merge: the CALLER's choice
    if config is not None:
        data_dir = data_dir if data_dir is not None else config.engine.data_dir
        host = host if host is not None else config.server.host
        port = port if port is not None else config.server.http_port
        engine_cfg = EngineConfig(
            space_write_buffer_size=config.engine.space_write_buffer_size,
            compaction_l0_trigger=config.engine.compaction_l0_trigger,
            compaction_workers=config.engine.compaction_workers,
            background_flush=config.engine.background_flush,
            flush_workers=config.engine.flush_workers,
            write_stall_immutable_count=(
                config.engine.write_stall_immutable_count
            ),
            write_stall_immutable_bytes=(
                config.engine.write_stall_immutable_bytes
            ),
            write_stall_deadline_s=config.engine.write_stall_deadline_s,
        )
        slow_threshold = config.limits.slow_threshold_s
        # the remote-engine client's per-hop ceiling follows the same
        # [limits] forward_timeout knob as the HTTP forwarding hops
        from ..remote.client import set_default_timeout

        set_default_timeout(config.limits.forward_timeout_s)
    host = host if host is not None else "127.0.0.1"
    port = port if port is not None else DEFAULT_HTTP_PORT
    if config is not None and config.s3.bucket and explicit_data_dir is not None:
        # Precedence rule: an explicit argument wins over config — an
        # explicitly passed data_dir keeps the node on local storage.
        logger.warning(
            "[s3] configured but an explicit data_dir was given; using local "
            "storage at %s and IGNORING the s3 section", explicit_data_dir,
        )
    if config is not None and config.s3.bucket and explicit_data_dir is None:
        # Cloud storage mode: SSTs, manifests, catalog, AND the WAL all
        # live in S3 — a diskless node (ref: the reference's cloud-native
        # deployment over object storage). Reads go through the CRC-paged
        # disk cache + sharded memory cache when configured.
        from ..db import Connection
        from ..engine.wal import ObjectStoreWal
        from ..utils.object_store import DiskCacheStore, MemCacheStore
        from ..utils.s3 import S3Store

        store = S3Store(
            config.s3.bucket,
            config.s3.endpoint,
            config.s3.access_key,
            config.s3.secret_key,
            region=config.s3.region,
            prefix=config.s3.prefix,
        )
        read_store = store
        if config.s3.disk_cache_dir:
            read_store = DiskCacheStore(
                read_store, config.s3.disk_cache_dir, config.s3.disk_cache_bytes
            )
        if config.s3.mem_cache_bytes:
            read_store = MemCacheStore(read_store, config.s3.mem_cache_bytes)
        conn = Connection(
            read_store,
            wal=(ObjectStoreWal(store) if config.engine.wal else None),
            config=engine_cfg,
        )
    else:
        conn = connect(
            data_dir,
            wal=(config.engine.wal if config is not None else True),
            engine_config=engine_cfg,
            wal_backend=(config.engine.wal_backend if config is not None else "disk"),
        )
    router = None
    cluster = None
    if config is not None and config.cluster.enabled:
        if config.cluster.meta_endpoints:
            # Coordinator mode (ref: setup.rs build_with_meta).
            from ..cluster import ClusterBasedRouter, ClusterImpl, MetaClient

            meta_client = MetaClient(config.cluster.meta_endpoints)
            cluster = ClusterImpl(conn, config.cluster.self_endpoint, meta_client)
            router = ClusterBasedRouter(cluster, meta_client)
        else:
            from ..cluster import RuleBasedRouter

            router = RuleBasedRouter(
                config.cluster.self_endpoint,
                config.cluster.endpoints,
                config.cluster.rules,
            )
    # gRPC services (remote engine + storage) alongside HTTP — the
    # reference's primary protocol (grpc/mod.rs:162-198). Port derives
    # from the HTTP port unless configured; -1 disables.
    grpc_server = None
    grpc_cfg = config.server.grpc_port if config is not None else 0
    if grpc_cfg >= 0:
        from ..remote import GrpcServer, grpc_endpoint_for

        derived = int(grpc_endpoint_for(f"{host}:{port}").rsplit(":", 1)[1])
        grpc_port = grpc_cfg if grpc_cfg > 0 else derived
        if grpc_cfg > 0 and grpc_cfg != derived:
            logger.warning(
                "grpc_port %d differs from the http_port+%d convention (%d): "
                "PEERS derive remote-engine endpoints from HTTP endpoints, so "
                "cross-node reads/writes to this node will fail — use the "
                "derived port unless every node overrides consistently",
                grpc_cfg, derived - port, derived,
            )
        grpc_server = GrpcServer(conn, host=host, port=grpc_port, cluster=cluster)

    if router is not None and grpc_server is not None:
        # Partitioned tables resolve partitions through ROUTED handles:
        # every operation re-resolves ownership via the router's TTL
        # cache, so a partition whose shard moves (rebalance, failover)
        # is followed instead of wedging on a pinned stale endpoint
        # (ref: remote_engine_client/src/cached_router.rs eviction).
        from ..remote.client import RoutedSubTable

        def resolve_sub(
            logical: str, index: int, sub_name: str, sub_id: int, local_open=None
        ):
            # Schema/options come from the sub-table's manifest in the
            # SHARED object store — no RPC, and no ordering dependency on
            # the remote node having loaded its registry yet.
            from ..engine.manifest import Manifest
            from ..engine.options import TableOptions as _TableOptions

            state = Manifest(conn.store, 0, sub_id).load()
            if state.schema is None:
                raise RuntimeError(f"manifest for {sub_name} missing schema")
            return RoutedSubTable(
                sub_name,
                state.schema,
                _TableOptions.from_dict(state.options),
                router=router,
                cluster=cluster,
                instance=conn.instance,
                local_open=local_open,
            )

        conn.catalog.sub_table_resolver = resolve_sub

    observability = (
        config.observability if config is not None else None
    )
    if observability is None:
        from ..utils.config import ObservabilitySection

        observability = ObservabilitySection()
    node = (
        config.cluster.self_endpoint
        if config is not None and config.cluster.enabled
        else "standalone"
    )
    app = create_app(
        conn,
        router=router,
        cluster=cluster,
        auth_token=(config.server.auth_token if config is not None else ""),
        limits=(config.limits if config is not None else None),
        observability=observability,
        node=node,
        rules_cfg=(config.rules if config is not None else None),
        slo_cfg=(config.slo if config is not None else None),
        read_staleness_s=(
            config.cluster.read_staleness_s if config is not None else 0.0
        ),
        batch_cfg=(config.wlm.batch if config is not None else None),
    )
    app["proxy"].slow_threshold_s = slow_threshold

    # MySQL / PostgreSQL wire listeners (ref: mysql/service.rs:21,
    # postgresql/service.rs:21; defaults 3307/5433, config.rs:176-179).
    # Non-overlapping derived bands (+2000 / +3000, like grpc's +1000)
    # avoid collisions when several nodes share a host. Both speak
    # through the shared SQL gateway — same routing/fences as HTTP.
    wire_servers = []
    gateway = app["sql_gateway"]
    mysql_cfg = config.server.mysql_port if config is not None else 0
    pg_cfg = config.server.pg_port if config is not None else 0
    if mysql_cfg >= 0:
        from .mysql import MysqlServer

        wire_servers.append(
            MysqlServer(gateway, host=host, port=mysql_cfg if mysql_cfg > 0 else port + 2000)
        )
    if pg_cfg >= 0:
        from .postgres import PostgresServer

        wire_servers.append(
            PostgresServer(gateway, host=host, port=pg_cfg if pg_cfg > 0 else port + 3000)
        )
    if wire_servers:
        async def _start_wire(app_):
            for s in wire_servers:
                try:
                    await s.start()
                except (OSError, OverflowError, ValueError) as e:
                    # A busy derived port must not take down the node's
                    # HTTP serving — wire listeners are best-effort.
                    # (OverflowError/ValueError: an HTTP port near the top
                    # of the range derives a +2000/+3000 port past 65535.)
                    logger.warning(
                        "wire listener %s failed to bind: %s",
                        type(s).__name__, e,
                    )

        async def _stop_wire(app_):
            for s in wire_servers:
                await s.stop()

        app.on_startup.append(_start_wire)
        app.on_cleanup.append(_stop_wire)

    if grpc_server is not None:
        async def _start_grpc(app_):
            grpc_server.start()

        async def _stop_grpc(app_):
            grpc_server.stop()

        app.on_startup.append(_start_grpc)
        app.on_cleanup.append(_stop_grpc)
    if cluster is not None:
        # Heartbeats begin only once we LISTEN: the coordinator may
        # dispatch open_shard the moment we register.
        async def _start_cluster(app_):
            await asyncio.get_running_loop().run_in_executor(None, cluster.start)

        async def _stop_cluster(app_):
            cluster.stop()

        app.on_startup.append(_start_cluster)
        app.on_cleanup.append(_stop_cluster)
    logger.info("horaedb_tpu http listening on %s:%d (data: %s)", host, port, data_dir)
    try:
        web.run_app(app, host=host, port=port, print=None)
    finally:
        conn.close()


def main() -> None:
    import argparse

    from ..utils.config import Config

    p = argparse.ArgumentParser(description="horaedb_tpu server")
    p.add_argument("--config", default=None, help="TOML config file")
    p.add_argument("--data-dir", default=None, help="storage dir (default: in-memory)")
    p.add_argument("--host", default=None)
    p.add_argument("--port", type=int, default=None)
    p.add_argument("--log-level", default="info")
    p.add_argument(
        "--log-file", default=None,
        help="also append logs to this file (ref: the tracing file appender)",
    )
    args = p.parse_args()
    handlers = None
    if args.log_file:
        handlers = [
            logging.StreamHandler(),
            logging.FileHandler(args.log_file),
        ]
    logging.basicConfig(level=args.log_level.upper(), handlers=handlers)
    from ..utils.compile_cache import enable_compile_cache

    logger.info("jax compilation cache: %s", enable_compile_cache())
    cfg = Config.load(args.config)
    # CLI flags override config file + env.
    if args.data_dir is not None:
        cfg.engine.data_dir = args.data_dir
    if args.host is not None:
        cfg.server.host = args.host
    if args.port is not None:
        cfg.server.http_port = args.port
    run_server(config=cfg)


if __name__ == "__main__":
    main()
