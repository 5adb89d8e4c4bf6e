"""tenantsim — the multi-tenant production simulator
(ROADMAP item 5: the arc's missing proof. Quotas, admission, stall
shedding, follower fencing, alerts, the event journal, and now the SLO
plane all exist; THIS harness exercises them together and asserts
success from the database's OWN tables, not harness-side timing).

    python -m horaedb_tpu.tools.tenantsim [--tenants 200] [--nodes 3]
        [--duration 45] [--seed 7] ...

What it builds — a REAL 1-meta + N-node cluster, in process:

- a MetaServer (+ aiohttp app) on a real port, with leases, rebalance
  and read-replica scheduling;
- N data nodes, each a full server app (create_app: SQL gateway, wlm
  admission/quota/dedup, rules engine, SLO evaluator) over its own
  ``FaultInjectingStore`` wrapping one SHARED on-disk store — the same
  shared-storage topology the subprocess cluster tests use, with the
  chaos knobs adjustable mid-run;
- node0 additionally runs the self-monitoring recorder (one recorder:
  the registry is process-global in-process), writing the cluster's
  telemetry into ``system_metrics.samples`` through the coordinator-
  serialized DDL + ordinary forwarded-write path.

What it drives — hundreds of simulated tenants with mixed TSBS-style
workloads over worker threads: cheap per-tenant dashboard queries
(frozen historical range with precomputed reference answers — ANY
served answer that disagrees is a wrong answer, whoever served it),
raw ORDER-BY-LIMIT panels, concurrent per-tenant ingest, PromQL reads,
and an expensive-scan storm phase.

The fault schedule (all deterministic under --seed): a store latency
burst, a store error burst (injected faults are themselves a metric —
``horaedb_object_store_injected_faults_total`` — so alerts and SLO
objectives observe the chaos through the database's own telemetry), a
leader KILL (heartbeats stop, HTTP stops, tables close WITHOUT flush —
unflushed rows survive only in the shared WAL for the new owner to
replay), a replica-lease flap (pause_heartbeats: leases lapse, shards
freeze, then thaw), a rolling shard migration — and, with ``--elastic``,
a HOT-TENANT SKEW phase: most dashboard traffic slams the tables
co-owned by one node while the [cluster.elastic] control loop on the
meta must scale replicas out, serve route=follower reads, execute a
pre-warmed leader move, and scale back in after the storm — all
asserted from ``system.public.events`` / ``query_stats``.

What it asserts — from the database's own tables:

- ``system.public.slo``: verdicts present and evaluated; the
  cheap-class p99 objective NEVER burned (admission kept the cheap lane
  flat through the expensive storm); the store-fault objective burned
  and recovered (full scale);
- ``system.public.alerts`` + ``system.public.events``: at least one
  alert fired AND resolved under the injected faults;
- ``system.public.events``: the retained seq window is contiguous and
  every missing leading seq is accounted by the drop counter
  (``horaedb_events_dropped_total`` / /debug/status events.dropped);
- zero wrong answers across every served read — follower, leader,
  post-kill, mid-flap;
- a sample of acknowledged writes (incl. rows acked by the killed
  leader) reads back after recovery.

The ~30s tier-1 smoke (tests/test_tenantsim.py) runs a small
configuration with one kill + one latency/error burst; the full scale
runs under ``@pytest.mark.slow``.
"""

from __future__ import annotations

import asyncio
import json
import logging
import math
import os
import random
import shutil
import socket
import tempfile
import threading
import time
import urllib.error
import urllib.request
from dataclasses import dataclass, field
from typing import Optional

logger = logging.getLogger("horaedb_tpu.tenantsim")


# ---------------------------------------------------------------------------
# configuration


@dataclass
class SimConfig:
    nodes: int = 3
    tenants: int = 200
    tables: int = 3
    duration_s: float = 45.0
    seed: int = 7
    workers: int = 6
    ingest_workers: int = 2
    read_replicas: int = 1
    num_shards: int = 0  # 0 = 2 * nodes
    rows_per_table: int = 30_000
    # observability cadence (fast: the sim must see verdicts move)
    scrape_interval_s: float = 0.4
    eval_interval_s: float = 0.4
    fast_window_s: float = 4.0
    slow_window_s: float = 16.0
    event_ring: int = 8192
    # cluster timing
    lease_ttl_s: float = 2.0
    heartbeat_timeout_s: float = 3.0
    meta_tick_s: float = 0.25
    # fault schedule (fractions of duration_s; None disables)
    storm_window: Optional[tuple] = (0.15, 0.45)
    # slow-storm-with-tight-deadlines phase (ISSUE 14): during the
    # window a slice of the expensive-scan traffic carries a tight
    # X-HoraeDB-Timeout-Ms budget while store latency is injected —
    # expired queries must answer the typed 504 within budget + one
    # checkpoint interval (generous slack for the contended 1-core
    # host), admission slots must drain back to baseline after, and
    # the cheap-class p99 objective must never burn through it
    deadline_phase: Optional[tuple] = None
    deadline_budget_ms: float = 150.0
    deadline_fraction: float = 0.35
    deadline_slack_s: float = 3.0
    latency_burst: Optional[tuple] = (0.2, 0.4)
    latency_burst_s: float = 0.03
    error_burst: Optional[tuple] = (0.3, 0.55)
    error_rate: float = 0.25
    kill_at: Optional[float] = 0.65
    lease_flap_at: Optional[float] = None  # needs >= 3 nodes to be gentle
    shard_move_at: Optional[float] = None
    # hot-tenant skew phase: a window where most dashboard traffic slams
    # the tables co-owned by ONE node — the elastic control loop's
    # standing gate (scale-out during, move off the hot node, scale-in
    # after the storm)
    hot_phase: Optional[tuple] = None
    hot_fraction: float = 0.75
    # elastic shard management ([cluster.elastic] on the meta): the
    # thresholds are in the inspector's units — query_stats rows per
    # second summed across nodes (in-process every node answers the one
    # shared ring, so counts read ~nodes x real qps)
    elastic: bool = False
    elastic_up_qps: float = 6.0
    elastic_down_qps: float = 1.5
    elastic_fast_window_s: float = 3.0
    elastic_slow_window_s: float = 8.0
    elastic_decide_s: float = 1.0
    elastic_cooldown_s: float = 2.0
    # workload shape
    quota_tenants: int = 2  # tenants given a deliberately tiny read quota
    settle_timeout_s: float = 25.0
    # cohort batching ([wlm.batch] on every node): the dashboard flood —
    # hundreds of tenants asking the same SELECT shape with different
    # literals — gathers in micro-batching windows and serves as fused
    # cohorts, so the standing multi-tenant gate exercises cohort
    # serving under faults. Default ON; --no-batch reproduces the
    # per-query dispatch path.
    batch: bool = True
    batch_window_s: float = 0.002
    batch_max_cohort: int = 32
    # decision plane (ISSUE 16): with dtype_auto the sim runs
    # HORAEDB_CACHE_DTYPE=auto plus a dedicated panel table whose value
    # column is only ever min/max'd by the workload — bf16-resident by
    # the tuner's own choice — and a post-run sum forces the graded
    # f32 PROMOTION the decision journal must carry
    dtype_auto: bool = False
    # live window state (ISSUE 18): the live open-tail panel becomes the
    # ELIGIBLE shape (time_bucket + tenant grouping over the open tail),
    # so hot panels promote to device-resident ring state under live
    # ingest, and post-run collection drives the journaled
    # promote -> serve -> equivalence -> evict walk as a standing gate;
    # --no-livewindow reproduces the raw-rescan panel path
    livewindow: bool = True


@dataclass
class SimReport:
    """Everything the acceptance gates read, plus color for humans."""

    config: dict = field(default_factory=dict)
    served: int = 0
    wrong_answers: int = 0
    unavailable: int = 0
    shed: int = 0
    quota_rejected: int = 0
    ingest_acked_rows: int = 0
    ingest_shed: int = 0
    qps: float = 0.0
    slo_rows: list = field(default_factory=list)
    slo_active_rows: int = 0
    cheap_objective_breaches: int = -1
    slo_burned_objectives: list = field(default_factory=list)
    slo_recovered_objectives: list = field(default_factory=list)
    alerts_fired: list = field(default_factory=list)
    alerts_resolved: list = field(default_factory=list)
    event_count: int = 0
    event_seq_gaps: int = -1
    event_drops_unaccounted: int = -1
    event_drops: int = 0
    follower_served: int = 0
    # deadline-storm gates (ISSUE 14), from the database's own tables
    deadline_sent: int = 0
    deadline_expired: int = 0
    deadline_overdue: int = 0
    deadline_timeout_events: int = -1
    deadline_timed_out_rows: int = -1
    admission_units_after: int = -1
    killed_node: str = ""
    kill_recovered: bool = False
    acked_rows_checked: int = 0
    acked_rows_missing: int = -1
    # elastic control loop (from system.public.events, the database's
    # own journal of the meta's decisions)
    elastic_scale_ups: int = 0
    elastic_scale_downs: int = 0
    elastic_moves: int = 0
    elastic_prewarmed_moves: int = 0
    elastic_prewarms: int = 0
    elastic_quarantines: int = 0
    elastic_move_expected: bool = False
    hot_tables: list = field(default_factory=list)
    # decision plane (ISSUE 16), from system.public.decisions +
    # system.public.calibration: per active loop, >= 1 resolved decision
    # row, a finite calibration verdict, and exact accounting
    # (issued == resolved + expired + unresolved)
    decision_active_loops: list = field(default_factory=list)
    decision_resolved_counts: dict = field(default_factory=dict)
    decision_counts: dict = field(default_factory=dict)
    calibration_verdicts: dict = field(default_factory=dict)
    decision_unaccounted: int = -1
    # live window state (ISSUE 18): open-tail panels must actually be
    # served from ring state (route=livewindow in query_stats) and the
    # state answer must agree with the kill-switch raw rescan
    livewindow_served: int = 0
    livewindow_equiv_checked: int = 0
    livewindow_equiv_ok: int = 0
    # profile plane (ISSUE 20), from system.public.profile: >= 1
    # attribution row per exercised serving plane, and span coverage
    # keeps the untracked fraction of root wall under the bound
    profile_route_rows: dict = field(default_factory=dict)
    profile_untracked_fraction: Optional[float] = None
    notes: list = field(default_factory=list)

    def violations(self) -> list[str]:
        """The acceptance gates (ISSUE 11): empty list = pass."""
        out = []
        if self.slo_active_rows <= 0:
            out.append("no evaluated SLO verdicts in system.public.slo")
        if self.cheap_objective_breaches != 0:
            out.append(
                "cheap-class p99 objective burned "
                f"{self.cheap_objective_breaches} time(s) (must stay flat)"
            )
        if self.wrong_answers != 0:
            out.append(f"{self.wrong_answers} wrong answer(s) served")
        if self.event_seq_gaps != 0:
            out.append(f"{self.event_seq_gaps} event-journal seq gap(s)")
        if self.event_drops_unaccounted != 0:
            out.append(
                f"{self.event_drops_unaccounted} event drop(s) unaccounted"
            )
        if self.config.get("error_burst") is not None:
            # only the error burst deterministically trips the
            # StoreFaults alert; without it, demanding one is a lie
            if not self.alerts_fired:
                out.append("no alert fired under injected faults")
            if not self.alerts_resolved:
                out.append("no alert resolved after the faults cleared")
        if self.acked_rows_missing != 0:
            out.append(
                f"{self.acked_rows_missing} acknowledged row(s) unreadable "
                "after recovery"
            )
        if self.killed_node and not self.kill_recovered:
            out.append(
                "frozen-range reads did not recover after the leader kill"
            )
        if self.config.get("elastic"):
            # the elastic gates, all asserted from the database's own
            # event journal: the hot phase must scale a hot shard OUT,
            # followers must actually serve, the hot shard must move
            # (when the skew made a skew-reducing move possible), and
            # capacity must come back IN after the storm
            if self.elastic_scale_ups < 1:
                out.append("elastic: no scale-up under the hot-tenant skew")
            if self.elastic_scale_downs < 1:
                out.append("elastic: no scale-in after the storm")
            if self.follower_served < 1:
                out.append("elastic: no route=follower reads served")
            if self.elastic_move_expected and self.elastic_moves < 1:
                out.append(
                    "elastic: hot shards co-owned by one node but no move"
                )
            if self.elastic_moves >= 1 and self.elastic_prewarmed_moves < 1:
                out.append(
                    "elastic: moves happened but none was pre-warmed "
                    "(target never tailed the manifest before cutover)"
                )
        if self.config.get("deadline_phase") is not None:
            # the deadline plane's gates (ISSUE 14): expired queries
            # answer the typed error within budget + one checkpoint
            # interval, the database's own journal/stats carry the
            # evidence, and the admission slots drain back to baseline
            if self.deadline_sent < 1:
                out.append("deadline storm never sent a budgeted query")
            if self.config.get("latency_burst") is not None:
                if self.deadline_expired < 1:
                    out.append(
                        "no query expired under the slow storm with "
                        "tight deadlines"
                    )
                if self.deadline_timeout_events < 1:
                    out.append(
                        "no query_timeout event in system.public.events"
                    )
                if self.deadline_timed_out_rows < 1:
                    out.append(
                        "no timed_out row in system.public.query_stats"
                    )
            if self.deadline_overdue != 0:
                out.append(
                    f"{self.deadline_overdue} expired quer(ies) answered "
                    "later than budget + checkpoint slack"
                )
            if self.admission_units_after > 1:
                # <= 1: the workload-reading SELECT itself holds one
                # cheap unit while it materializes the table
                out.append(
                    "admission slots leaked after the deadline storm "
                    f"(units_in_use={self.admission_units_after})"
                )
        # the decision plane's standing gate (ISSUE 16): every ACTIVE
        # adaptive loop shows decision rows and a finite calibration
        # verdict from the database's own tables, and the journal's
        # accounting reconciles exactly — zero unaccounted decisions
        for loop in self.decision_active_loops:
            if self.decision_resolved_counts.get(loop, 0) < 1:
                out.append(
                    f"decision plane: no resolved {loop} decision in "
                    "system.public.decisions"
                )
            if not self.calibration_verdicts.get(loop):
                out.append(
                    f"decision plane: no finite {loop} calibration "
                    "verdict in system.public.calibration"
                )
        if self.config.get("livewindow"):
            # live window state (ISSUE 18): the panel shape must have
            # been served from ring state and checked against the raw
            # rescan (a mismatch already counted as a wrong answer)
            if self.livewindow_served < 1:
                out.append(
                    "live window state: no route=livewindow read served"
                )
            if self.livewindow_equiv_checked < 1:
                out.append(
                    "live window state: state/raw equivalence never checked"
                )
        if self.decision_active_loops and self.decision_unaccounted != 0:
            out.append(
                f"decision plane: {self.decision_unaccounted} decision(s) "
                "unaccounted (issued != resolved + expired + unresolved)"
            )
        # the profile plane's standing gate (ISSUE 20): the database
        # attributes its own wall-clock — every serving plane the sim
        # exercises shows attribution rows in system.public.profile, and
        # span coverage keeps the untracked fraction of root wall small
        # (a large fraction IS the signal a plane lost its spans)
        for route in ("query", "ingest", "flush", "compaction", "rules"):
            if self.profile_route_rows.get(route, 0) < 1:
                out.append(
                    "profile plane: no system.public.profile row for "
                    f"route={route}"
                )
        if (self.profile_untracked_fraction is not None
                and self.profile_untracked_fraction >= 0.40):
            out.append(
                "profile plane: untracked fraction "
                f"{self.profile_untracked_fraction} >= 0.40 of root wall"
            )
        if self.served == 0:
            out.append("no queries served at all")
        return out

    def to_dict(self) -> dict:
        d = dict(self.__dict__)
        d["violations"] = self.violations()
        d["slo_rows"] = self.slo_rows  # already plain dicts
        return d


# ---------------------------------------------------------------------------
# HTTP helpers (blocking; used from worker threads)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(method, url, payload=None, timeout=20.0, headers=None):
    """(status, body); connection-level failures (refused, socket
    timeout, reset) come back as a synthetic 599 instead of raising, so
    every phase — seeding retries, workers, collection right after a
    kill — handles 'node unreachable' the same way it handles a 5xx."""
    data = json.dumps(payload).encode() if payload is not None else None
    req = urllib.request.Request(
        url, data=data,
        headers={"Content-Type": "application/json", **(headers or {})},
        method=method,
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read().decode() or "{}")
    except urllib.error.HTTPError as e:
        try:
            return e.code, json.loads(e.read().decode() or "{}")
        except Exception:
            return e.code, {}
    except (urllib.error.URLError, OSError, TimeoutError) as e:
        return 599, {"error": f"unreachable: {e}"}


def _wait_until(fn, timeout=60.0, interval=0.1, desc="condition"):
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        try:
            last = fn()
            if last:
                return last
        except Exception as e:
            last = e
        time.sleep(interval)
    raise TimeoutError(f"timed out waiting for {desc}: last={last!r}")


def _rows_agree(a: list, b: list, rtol: float = 1e-3) -> bool:
    if len(a) != len(b):
        return False
    for ra, rb in zip(a, b):
        if set(ra) != set(rb):
            return False
        for k in ra:
            va, vb = ra[k], rb[k]
            if isinstance(va, float) or isinstance(vb, float):
                if not math.isclose(
                    float(va), float(vb), rel_tol=rtol, abs_tol=1e-6
                ):
                    return False
            elif va != vb:
                return False
    return True


# ---------------------------------------------------------------------------
# the in-process cluster


class _AppHost:
    """One aiohttp app on ITS OWN event-loop thread with a dedicated
    default executor. One shared loop for meta + N nodes starves on a
    1-core host (a node's blocking work queues ahead of meta heartbeat
    handlers → leases lapse → the whole cluster fences itself); separate
    loops make each server's responsiveness depend only on the GIL, like
    separate processes do."""

    def __init__(self, name: str, executor_workers: int = 16) -> None:
        self.name = name
        self.loop: Optional[asyncio.AbstractEventLoop] = None
        self.runner = None
        self.site = None
        self._thread: Optional[threading.Thread] = None
        self._workers = executor_workers

    def start(self, app, port: int) -> None:
        from concurrent.futures import ThreadPoolExecutor

        from aiohttp import web

        ready = threading.Event()

        def run():
            loop = asyncio.new_event_loop()
            asyncio.set_event_loop(loop)
            loop.set_default_executor(
                ThreadPoolExecutor(
                    max_workers=self._workers,
                    thread_name_prefix=f"{self.name}-exec",
                )
            )
            self.loop = loop
            ready.set()
            loop.run_forever()

        self._thread = threading.Thread(
            target=run, name=f"tsim-{self.name}", daemon=True
        )
        self._thread.start()
        ready.wait(10)

        async def up():
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", port)
            await site.start()
            return runner, site

        self.runner, self.site = self.call(up())

    def call(self, coro, timeout=60.0):
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result(timeout)

    def stop_site(self) -> None:
        if self.site is not None:
            self.call(self.site.stop())
            self.site = None

    def close(self) -> None:
        try:
            if self.runner is not None:
                self.call(self.runner.cleanup(), timeout=30)
        except Exception:
            logger.exception("%s runner cleanup", self.name)
        if self.loop is not None:
            self.loop.call_soon_threadsafe(self.loop.stop)
            self._thread.join(timeout=10)


class SimNode:
    def __init__(self, endpoint, conn, cluster, router, app, fault_store,
                 host: _AppHost):
        self.endpoint = endpoint
        self.port = int(endpoint.rsplit(":", 1)[1])
        self.conn = conn
        self.cluster = cluster
        self.router = router
        self.app = app
        self.fault_store = fault_store
        self.host = host
        self.alive = True


class SimCluster:
    """1 meta + N data nodes, in process, over one shared disk store."""

    def __init__(self, cfg: SimConfig, root: Optional[str] = None) -> None:
        self.cfg = cfg
        self.root = root or tempfile.mkdtemp(prefix="tenantsim_")
        self._own_root = root is None
        self.meta_port = _free_port()
        self.meta_endpoint = f"127.0.0.1:{self.meta_port}"
        self.meta_server = None
        self.meta_host: Optional[_AppHost] = None
        self.nodes: list[SimNode] = []

    # -- construction ------------------------------------------------------

    def start(self) -> "SimCluster":
        from ..meta.service import MetaServer, create_meta_app

        cfg = self.cfg
        elastic = None
        if cfg.elastic:
            from ..utils.config import ElasticSection

            elastic = ElasticSection(
                enabled=True,
                min_replicas=cfg.read_replicas,
                max_replicas=max(cfg.read_replicas + 1, 2),
                scale_up_qps=cfg.elastic_up_qps,
                scale_down_qps=cfg.elastic_down_qps,
                fast_window_s=cfg.elastic_fast_window_s,
                slow_window_s=cfg.elastic_slow_window_s,
                decide_interval_s=cfg.elastic_decide_s,
                cooldown_s=cfg.elastic_cooldown_s,
                node_stable_s=1.0,
                min_move_qps=cfg.elastic_down_qps,
                prewarm_timeout_s=8.0,
                telemetry_timeout_s=2.0,
            )
        self.meta_server = MetaServer(
            num_shards=cfg.num_shards or 2 * cfg.nodes,
            lease_ttl_s=cfg.lease_ttl_s,
            heartbeat_timeout_s=cfg.heartbeat_timeout_s,
            read_replicas=cfg.read_replicas,
            elastic=elastic,
        )
        self.meta_server.start_loop(interval_s=cfg.meta_tick_s)
        self.meta_host = _AppHost("meta", executor_workers=8)
        self.meta_host.start(create_meta_app(self.meta_server), self.meta_port)

        for i in range(cfg.nodes):
            node = self._build_node(i)
            node.host.start(node.app, node.port)
            # heartbeats begin once we listen (run_server's ordering)
            node.cluster.start()
            self.nodes.append(node)

        for node in self.nodes:
            _wait_until(
                lambda n=node: _http(
                    "GET", f"http://{n.endpoint}/health", timeout=2
                )[0] == 200,
                desc=f"node {node.endpoint} health",
            )

        def shards_assigned():
            s, body = _http(
                "GET", f"http://{self.meta_endpoint}/meta/v1/shards", timeout=2
            )
            return (
                s == 200
                and body.get("shards")
                and all(sh["node"] for sh in body["shards"])
            ) or None

        _wait_until(shards_assigned, desc="shards assigned")
        return self

    def _build_node(self, i: int) -> SimNode:
        from ..cluster import ClusterBasedRouter, ClusterImpl, MetaClient
        from ..db import Connection
        from ..engine.instance import EngineConfig
        from ..engine.wal import LocalDiskWal
        from ..server import create_app
        from ..utils.config import (
            LimitsConfig,
            ObservabilitySection,
            RulesSection,
            SloSection,
        )
        from ..utils.object_store import FaultInjectingStore, LocalDiskStore

        cfg = self.cfg
        port = _free_port()
        endpoint = f"127.0.0.1:{port}"
        store_root = os.path.join(self.root, "store")
        fault_store = FaultInjectingStore(
            LocalDiskStore(store_root), seed=cfg.seed * 1000 + i
        )
        conn = Connection(
            fault_store,
            wal=LocalDiskWal(os.path.join(store_root, "wal")),
            config=EngineConfig(
                # small buffers so live ingest actually flushes (flush
                # traffic is what the store faults bite)
                space_write_buffer_size=8 << 20,
                write_stall_deadline_s=3.0,
            ),
        )
        meta_client = MetaClient([self.meta_endpoint])
        cluster = ClusterImpl(
            conn, endpoint, meta_client,
            heartbeat_interval_s=min(0.5, cfg.lease_ttl_s / 3),
        )
        router = ClusterBasedRouter(cluster, meta_client, cache_ttl_s=1.0)
        # rules + SLO everywhere (eval-on-owner decides who actually
        # evaluates — the samples shard lands where meta puts it); the
        # RECORDER only on node0: the metrics registry is process-global
        # in-process, N recorders would write N copies of one registry
        rules_cfg = RulesSection(
            eval_interval_s=cfg.eval_interval_s,
            alerts=[
                "StoreFaults := rate(horaedb_object_store_injected_faults_total[10s]) > 0.01",
            ],
        )
        slo_cfg = SloSection(
            objectives=self.objective_lines(),
            fast_window_s=cfg.fast_window_s,
            slow_window_s=cfg.slow_window_s,
        )
        observability = None
        if i == 0:
            observability = ObservabilitySection(
                self_scrape=True,
                self_scrape_interval_s=cfg.scrape_interval_s,
                event_ring=cfg.event_ring,
            )
        from ..utils.config import BatchSection

        app = create_app(
            conn,
            router=router,
            cluster=cluster,
            limits=LimitsConfig(admission_deadline_s=2.0),
            observability=observability,
            node=endpoint,
            rules_cfg=rules_cfg,
            slo_cfg=slo_cfg,
            batch_cfg=BatchSection(
                enabled=cfg.batch,
                window_s=cfg.batch_window_s,
                max_cohort=cfg.batch_max_cohort,
            ),
        )
        return SimNode(
            endpoint, conn, cluster, router, app, fault_store,
            _AppHost(f"node{i}"),
        )

    def objective_lines(self) -> list[str]:
        """The sim's declared SLOs. cheap_p99 is the headline: the cheap
        admission lane's end-to-end p99 must stay flat while the
        expensive storm rages (the bound is generous for a loaded CI
        host — FLAT is the claim, not FAST). store_faults burns during
        the error burst and recovers — proof the burn/recover machinery
        trips on real injected chaos. rules_alive is the alert-pipeline
        freshness guard: the alert evaluator itself must keep evaluating."""
        lines = [
            "cheap_p99 := histogram_quantile(0.99, "
            'rate(horaedb_query_class_duration_seconds_bucket{class="cheap"}[10s])'
            ") <= 2.5 target 75%",
            "store_faults := rate("
            "horaedb_object_store_injected_faults_total[10s]) <= 0.01 "
            "target 75%",
            "shed_ratio := rate(horaedb_admission_shed_total[10s]) <= 5 "
            "target 75%",
            'rules_alive := rate(horaedb_rules_eval_total{kind="alert"}[15s])'
            " >= 0.01 target 50%",
        ]
        if self.cfg.read_replicas > 0:
            # the follower watermark is "last installed flush", so its lag
            # tracks DATA age, not wall-clock replication delay — the
            # seeded history is hours old by construction. The bound
            # asserts the tail pipeline isn't wedged, nothing tighter.
            lines.append(
                "replica_lag := horaedb_replica_watermark_lag_seconds "
                "<= 14400 target 50%"
            )
        return lines

    # -- fault injection ---------------------------------------------------

    def set_store_latency(self, seconds: float) -> None:
        for n in self.nodes:
            n.fault_store.put_latency_s = seconds
            n.fault_store.get_latency_s = seconds / 2

    def set_store_errors(self, rate: float) -> None:
        for n in self.nodes:
            n.fault_store.error_rate = rate

    def samples_owner(self) -> Optional[SimNode]:
        from ..engine.metrics_recorder import SAMPLES_TABLE

        for n in self.nodes:
            if n.alive and n.cluster.owns_table(SAMPLES_TABLE):
                return n
        return None

    def kill_node(self, node: SimNode) -> None:
        """A kill, not a shutdown: stop serving and stop heartbeats, then
        close table handles WITHOUT flushing (WAL mode) — acknowledged
        unflushed rows survive only in the shared WAL, exactly what a
        dead process leaves behind; the coordinator times the node out
        and the next owner replays. (In-process we must close handles —
        a zombie background flush racing the new owner's manifest is the
        one thing a real SIGKILL cannot do.)"""
        node.alive = False
        node.host.stop_site()
        node.cluster.stop()
        for shard in list(node.cluster.shard_set.all_shards()):
            try:
                node.cluster.close_shard(shard.shard_id, version=None)
            except Exception:
                logger.exception("closing shard on killed node")

    def migrate_some_shard(self, avoid_tables: set) -> Optional[int]:
        """Rolling move: migrate one shard holding none of
        ``avoid_tables`` (resolved to shard ids via the meta route) to
        another live node."""
        avoid_ids = set()
        for t in avoid_tables:
            s, body = _http(
                "GET", f"http://{self.meta_endpoint}/meta/v1/route/{t}",
                timeout=5,
            )
            if s == 200 and body.get("shard_id") is not None:
                avoid_ids.add(int(body["shard_id"]))
        s, body = _http(
            "GET", f"http://{self.meta_endpoint}/meta/v1/shards", timeout=5
        )
        if s != 200:
            return None
        live = {n.endpoint for n in self.nodes if n.alive}
        for sh in body.get("shards", []):
            if sh["shard_id"] in avoid_ids or sh["node"] not in live:
                continue
            if not sh.get("table_ids"):
                continue  # moving an empty shard proves nothing
            targets = [ep for ep in live if ep != sh["node"]]
            if not targets:
                return None
            s2, _ = _http(
                "POST",
                f"http://{self.meta_endpoint}/meta/v1/shard/migrate",
                {"shard_id": sh["shard_id"], "to_node": targets[0]},
                timeout=30,
            )
            if s2 == 200:
                return sh["shard_id"]
        return None

    def alive_endpoints(self) -> list[str]:
        return [n.endpoint for n in self.nodes if n.alive]

    # -- teardown ----------------------------------------------------------

    def close(self) -> None:
        for node in self.nodes:
            try:
                if node.alive:
                    node.cluster.stop()
            except Exception:
                pass
        try:
            if self.meta_server is not None:
                self.meta_server.stop()
        except Exception:
            pass
        for node in self.nodes:
            node.host.close()
        if self.meta_host is not None:
            self.meta_host.close()
        for node in self.nodes:
            try:
                node.conn.close()
            except Exception:
                pass
        if self._own_root:
            shutil.rmtree(self.root, ignore_errors=True)


# ---------------------------------------------------------------------------
# the simulation


class TenantSim:
    def __init__(self, cfg: SimConfig, cluster: Optional[SimCluster] = None):
        self.cfg = cfg
        self.cluster = cluster or SimCluster(cfg)
        self._own_cluster = cluster is None
        self.report = SimReport(config=dict(cfg.__dict__))
        self.rng = random.Random(cfg.seed)
        self._stop = threading.Event()
        self._storm = threading.Event()
        self._hot = threading.Event()  # hot-tenant skew phase active
        self._deadline = threading.Event()  # tight-budget storm active
        self._hot_refs: list = []  # reference queries on the hot tables
        self._lock = threading.Lock()
        self._acked: list[tuple[str, str, int, float]] = []  # table, tenant, ts, v
        self._refs: list[tuple[str, str, list]] = []  # sql, table, ref rows
        self.fence_ms = 0
        self._events_before: dict = {}
        self._t0_ms = 0

    # -- helpers -----------------------------------------------------------

    def _table(self, j: int) -> str:
        return f"tsim_cpu{j}"

    def _dtype_table(self) -> str:
        return "tsim_dstat"

    def _dtype_minmax_sql(self) -> str:
        # the dtype table's ONLY workload shape: min/max, never sum —
        # under HORAEDB_CACHE_DTYPE=auto the tuner stores v bf16
        return (
            f"SELECT host, min(v) AS mn, max(v) AS mx FROM "
            f"{self._dtype_table()} GROUP BY host"
        )

    def _dtype_sum_sql(self) -> str:
        # the usage GROWTH that forces the graded f32 promotion
        return (
            f"SELECT host, sum(v) AS s, max(v) AS mx FROM "
            f"{self._dtype_table()} GROUP BY host"
        )

    def _sql(self, endpoint: str, query: str, tenant: str = "default",
             timeout: float = 20.0, timeout_ms: Optional[float] = None):
        headers = {}
        if tenant != "default":
            headers["X-HoraeDB-Tenant"] = tenant
        if timeout_ms is not None:
            # the per-request time budget (deadline plane, ISSUE 14)
            headers["X-HoraeDB-Timeout-Ms"] = str(int(timeout_ms))
        return _http(
            "POST", f"http://{endpoint}/sql", {"query": query},
            timeout=timeout, headers=headers,
        )

    def _owner(self, table: str) -> str:
        s, body = _http(
            "GET",
            f"http://{self.cluster.meta_endpoint}/meta/v1/route/{table}",
            timeout=5,
        )
        if s == 200 and body.get("node"):
            return body["node"]
        return self.cluster.alive_endpoints()[0]

    # -- setup -------------------------------------------------------------

    def _seed_call(self, method, url, payload, desc, timeout_s=15.0):
        """Setup-phase HTTP with retries: a write issued right after the
        meta DDL can land in the not-yet-leased window of a freshly
        opened shard (503 fence) — retryable by contract."""
        deadline = time.monotonic() + timeout_s
        last = None
        while time.monotonic() < deadline:
            s, out = _http(method, url, payload, timeout=60)
            if s == 200:
                return out
            last = (s, out)
            time.sleep(0.3)
        raise AssertionError(f"{desc} failed: {last}")

    def seed_data(self) -> None:
        cfg = self.cfg
        eps = self.cluster.alive_endpoints()
        for j in range(cfg.tables):
            name = self._table(j)
            ddl = (
                f"CREATE TABLE {name} (tenant string TAG, host string TAG, "
                "v double, ts timestamp NOT NULL, TIMESTAMP KEY(ts)) "
                "ENGINE=Analytic WITH (update_mode='append', "
                "segment_duration='2h', write_buffer_size='2mb')"
            )
            self._seed_call(
                "POST", f"http://{eps[0]}/sql", {"query": ddl},
                desc=f"DDL {name}",
            )
        base = int(time.time() * 1000) - 2 * 3600_000
        rng = random.Random(cfg.seed + 1)
        max_ts = base
        for j in range(cfg.tables):
            name = self._table(j)
            owner = self._owner(name)
            rows = []
            for i in range(cfg.rows_per_table):
                tenant = i % cfg.tenants
                ts = base + (i // cfg.tenants) * 631 + tenant
                max_ts = max(max_ts, ts)
                rows.append(
                    {
                        "tenant": f"t{tenant}",
                        "host": f"h{i % 17}",
                        "v": round(rng.gauss(10.0, 3.0), 4),
                        # unique ts per (table, tenant): deterministic
                        # ORDER BY ts results even among same-tenant rows
                        "ts": ts,
                    }
                )
            for lo in range(0, len(rows), 2000):
                self._seed_call(
                    "POST", f"http://{owner}/write",
                    {"table": name, "rows": rows[lo : lo + 2000]},
                    desc=f"seed write {name}",
                )
            self._seed_call(
                "POST", f"http://{owner}/admin/flush?table={name}", {},
                desc=f"seed flush {name}",
            )
        # the frozen range ends AT the seeded data (a future-reaching
        # range would never be watermark-covered, so followers could
        # never serve it — the fence is what makes them eligible)
        self.fence_ms = max_ts + 1
        # reference answers for the frozen range — computed ONCE, before
        # any fault: any later disagreement is a wrong answer
        n_refs = min(cfg.tenants, 40)
        picked = rng.sample(range(cfg.tenants), n_refs)
        for t in picked:
            j = t % cfg.tables
            name = self._table(j)
            agg = (
                f"SELECT count(v) AS c, sum(v) AS s FROM {name} "
                f"WHERE tenant = 't{t}' AND ts < {self.fence_ms}"
            )
            raw = (
                f"SELECT v, ts FROM {name} WHERE tenant = 't{t}' "
                f"AND ts < {self.fence_ms} ORDER BY ts DESC LIMIT 10"
            )
            for q in (agg, raw):
                out = self._seed_call(
                    "POST", f"http://{eps[0]}/sql", {"query": q},
                    desc=f"reference query for t{t}",
                )
                self._refs.append((q, name, out["rows"]))
        if cfg.dtype_auto:
            # the dtype-tuner panel table: seeded once, flushed, never
            # ingested into (a stable base fingerprint so the scan cache
            # can build), and only ever min/max'd by the workload
            name = self._dtype_table()
            self._seed_call(
                "POST", f"http://{eps[0]}/sql",
                {"query": (
                    f"CREATE TABLE {name} (tenant string TAG, host string "
                    "TAG, v double, ts timestamp NOT NULL, "
                    "TIMESTAMP KEY(ts)) ENGINE=Analytic WITH "
                    "(update_mode='append', segment_duration='2h', "
                    "write_buffer_size='2mb')"
                )},
                desc=f"DDL {name}",
            )
            owner = self._owner(name)
            drng = random.Random(cfg.seed + 31)
            rows = [
                {
                    "tenant": f"t{i % cfg.tenants}",
                    "host": f"h{i % 17}",
                    "v": round(drng.gauss(10.0, 3.0), 4),
                    "ts": base + i * 977,
                }
                for i in range(1500)
            ]
            self._seed_call(
                "POST", f"http://{owner}/write",
                {"table": name, "rows": rows}, desc=f"seed write {name}",
            )
            self._seed_call(
                "POST", f"http://{owner}/admin/flush?table={name}", {},
                desc=f"seed flush {name}",
            )
        # deliberately tiny read quota for a few tenants: quota_reject
        # events + 429s are part of the workload the plane must absorb
        for t in range(min(cfg.quota_tenants, cfg.tenants)):
            for ep in eps:
                _http(
                    "POST", f"http://{ep}/admin/quota",
                    {"scope": "tenant", "name": f"tq{t}", "kind": "read_qps",
                     "rate": 0.5, "burst": 1},
                    timeout=10,
                )

    # -- workload ----------------------------------------------------------

    def _query_worker(self, wid: int) -> None:
        cfg = self.cfg
        rng = random.Random(cfg.seed * 7919 + wid)
        i = 0
        while not self._stop.is_set():
            eps = self.cluster.alive_endpoints()
            if not eps:
                time.sleep(0.2)
                continue
            ep = eps[(i + wid) % len(eps)]
            i += 1
            roll = rng.random()
            try:
                if self._deadline.is_set() and roll < cfg.deadline_fraction:
                    # slow-storm-with-tight-deadlines: the SAME
                    # expensive scan shape the storm runs, but carrying
                    # a budget far below what it costs under injected
                    # store latency — the typed 504 must come back
                    # within budget + one checkpoint interval, and the
                    # database's own journal/stats must show it
                    j = rng.randrange(cfg.tables)
                    q = (
                        f"SELECT tenant, count(v) AS c, sum(v) AS s, "
                        f"min(v) AS mn, max(v) AS mx FROM {self._table(j)} "
                        "GROUP BY tenant"
                    )
                    t_send = time.monotonic()
                    s, _ = self._sql(
                        ep, q, tenant="storm", timeout=30,
                        timeout_ms=cfg.deadline_budget_ms,
                    )
                    elapsed = time.monotonic() - t_send
                    with self._lock:
                        self.report.deadline_sent += 1
                        if s == 504:
                            self.report.deadline_expired += 1
                            if elapsed > (
                                cfg.deadline_budget_ms / 1000.0
                                + cfg.deadline_slack_s
                            ):
                                self.report.deadline_overdue += 1
                    if s != 504:
                        self._note_status(s, checked=False, ok=True)
                elif (
                    self._hot.is_set()
                    and self._hot_refs
                    and roll < cfg.hot_fraction
                ):
                    # hot-tenant skew: most dashboard traffic slams the
                    # tables co-owned by one node (known answers — the
                    # elastic machinery must scale/move WITHOUT a single
                    # wrong answer)
                    q, _table, ref = self._hot_refs[
                        (i * 13 + wid) % len(self._hot_refs)
                    ]
                    s, out = self._sql(ep, q, timeout=20)
                    if s == 200:
                        self._note_status(
                            s, checked=True,
                            ok=_rows_agree(out.get("rows", []), ref),
                        )
                    else:
                        self._note_status(s, checked=False, ok=True)
                elif self._storm.is_set() and roll < 0.25:
                    # expensive-scan storm: full-table multi-agg group-by
                    j = rng.randrange(cfg.tables)
                    q = (
                        f"SELECT tenant, count(v) AS c, sum(v) AS s, "
                        f"min(v) AS mn, max(v) AS mx FROM {self._table(j)} "
                        "GROUP BY tenant"
                    )
                    s, _ = self._sql(ep, q, tenant="storm", timeout=30)
                    self._note_status(s, checked=False, ok=True)
                elif roll < 0.6:
                    # cheap dashboard with a known answer
                    q, _tenant, ref = self._refs[
                        (i * 13 + wid) % len(self._refs)
                    ]
                    s, out = self._sql(ep, q, timeout=20)
                    if s == 200:
                        self._note_status(
                            s, checked=True,
                            ok=_rows_agree(out.get("rows", []), ref),
                        )
                    else:
                        self._note_status(s, checked=False, ok=True)
                elif roll < 0.75:
                    # quota-capped tenants: 429s by design
                    t = rng.randrange(max(1, cfg.quota_tenants))
                    j = rng.randrange(cfg.tables)
                    q = (
                        f"SELECT count(v) AS c FROM {self._table(j)} "
                        f"WHERE tenant = 't{t}'"
                    )
                    s, _ = self._sql(ep, q, tenant=f"tq{t}", timeout=20)
                    self._note_status(s, checked=False, ok=True)
                elif roll < 0.9:
                    # live open-tail panel (no fixed reference; exercises
                    # the leader-only path + follower refusal/fallback).
                    # With livewindow on this is the ELIGIBLE shape —
                    # time_bucket grouping over the open tail — so hot
                    # panels promote to ring state under live ingest;
                    # the tenant literal varies but the shape key does
                    # not, so every worker's read counts toward the
                    # promotion threshold
                    t = rng.randrange(cfg.tenants)
                    j = rng.randrange(cfg.tables)
                    if cfg.livewindow:
                        q = self._livewindow_panel_sql(j, tenant=t)
                    else:
                        q = (
                            f"SELECT count(v) AS c FROM {self._table(j)} "
                            f"WHERE tenant = 't{t}'"
                        )
                    s, _ = self._sql(ep, q, tenant=f"t{t}", timeout=20)
                    self._note_status(s, checked=False, ok=True)
                elif cfg.dtype_auto and roll >= 0.95:
                    # min/max-only panel on the dtype table — the usage
                    # the auto tuner learns bf16 from; the sum that
                    # forces the graded promotion runs at collection
                    s, _ = self._sql(ep, self._dtype_minmax_sql(),
                                     timeout=20)
                    self._note_status(s, checked=False, ok=True)
                else:
                    # PromQL over the self-monitoring history
                    s, _ = _http(
                        "GET",
                        f"http://{ep}/prom/v1/query?query="
                        "rate(horaedb_queries_total%5B30s%5D)",
                        timeout=20,
                    )
                    self._note_status(s, checked=False, ok=True)
            except Exception:
                with self._lock:
                    self.report.unavailable += 1

    def _note_status(self, status: int, checked: bool, ok: bool) -> None:
        with self._lock:
            if status == 200:
                if checked and not ok:
                    self.report.wrong_answers += 1
                else:
                    self.report.served += 1
            elif status == 503:
                self.report.shed += 1
            elif status == 429:
                self.report.quota_rejected += 1
            else:
                self.report.unavailable += 1

    def _ingest_worker(self, wid: int) -> None:
        cfg = self.cfg
        rng = random.Random(cfg.seed * 104729 + wid)
        seq = 0
        while not self._stop.is_set():
            eps = self.cluster.alive_endpoints()
            if not eps:
                time.sleep(0.2)
                continue
            ep = eps[(seq + wid) % len(eps)]
            j = rng.randrange(cfg.tables)
            name = self._table(j)
            now = int(time.time() * 1000)
            rows = []
            for k in range(100):
                t = rng.randrange(cfg.tenants)
                rows.append(
                    {
                        "tenant": f"t{t}",
                        "host": f"h{k % 17}",
                        "v": round(rng.gauss(10.0, 3.0), 4),
                        # strictly beyond the fence: the frozen reference
                        # range must never change under live ingest
                        "ts": max(now, self.fence_ms + 1)
                        + wid * 1_000_000 + seq * 200 + k,
                    }
                )
            seq += 1
            try:
                s, _ = _http(
                    "POST", f"http://{ep}/write",
                    {"table": name, "rows": rows}, timeout=20,
                )
            except Exception:
                with self._lock:
                    self.report.unavailable += 1
                continue
            with self._lock:
                if s == 200:
                    self.report.ingest_acked_rows += len(rows)
                    r = rows[0]
                    self._acked.append((name, r["tenant"], r["ts"], r["v"]))
                    if len(self._acked) > 512:
                        self._acked.pop(0)
                elif s in (503, 429):
                    self.report.ingest_shed += 1
                else:
                    self.report.unavailable += 1
            time.sleep(0.02)

    # -- the run -----------------------------------------------------------

    def run(self) -> SimReport:
        from ..utils.events import EVENT_STORE

        cfg = self.cfg
        prior_dtype = os.environ.get("HORAEDB_CACHE_DTYPE")
        # the live-window store is process-global: start from a clean
        # slate so promotions observed here are THIS run's promotions
        from ..state.livewindow import STORE as _lw_store

        _lw_store.clear()
        try:
            if cfg.dtype_auto:
                # the learned per-column dtype mode (the scan cache is
                # process-global, so the env knob reaches every node)
                os.environ["HORAEDB_CACHE_DTYPE"] = "auto"
            if self._own_cluster:
                self.cluster.start()
            self._events_before = EVENT_STORE.stats()
            self._t0_ms = int(time.time() * 1000)
            self.seed_data()
            t0 = time.monotonic()

            threads = [
                threading.Thread(
                    target=self._query_worker, args=(w,), daemon=True,
                    name=f"tsim-q{w}",
                )
                for w in range(cfg.workers)
            ] + [
                threading.Thread(
                    target=self._ingest_worker, args=(w,), daemon=True,
                    name=f"tsim-i{w}",
                )
                for w in range(cfg.ingest_workers)
            ]
            for th in threads:
                th.start()
            self._fault_schedule(t0)
            self._stop.set()
            for th in threads:
                th.join(timeout=10)
            elapsed = time.monotonic() - t0
            self.report.qps = round(self.report.served / elapsed, 1)
            self._settle()
            self._collect()
        finally:
            if cfg.dtype_auto:
                if prior_dtype is None:
                    os.environ.pop("HORAEDB_CACHE_DTYPE", None)
                else:
                    os.environ["HORAEDB_CACHE_DTYPE"] = prior_dtype
            if self._own_cluster:
                self.cluster.close()
        return self.report

    def _fault_schedule(self, t0: float) -> None:
        """The deterministic chaos timeline, expressed as (when, what)
        and walked in order while the workload runs."""
        cfg = self.cfg
        D = cfg.duration_s
        events: list[tuple[float, str]] = []
        if cfg.storm_window:
            events += [(cfg.storm_window[0] * D, "storm_on"),
                       (cfg.storm_window[1] * D, "storm_off")]
        if cfg.latency_burst:
            events += [(cfg.latency_burst[0] * D, "latency_on"),
                       (cfg.latency_burst[1] * D, "latency_off")]
        if cfg.error_burst:
            events += [(cfg.error_burst[0] * D, "errors_on"),
                       (cfg.error_burst[1] * D, "errors_off")]
        if cfg.kill_at is not None:
            events.append((cfg.kill_at * D, "kill"))
        if cfg.lease_flap_at is not None:
            events.append((cfg.lease_flap_at * D, "flap"))
        if cfg.shard_move_at is not None:
            events.append((cfg.shard_move_at * D, "move"))
        if cfg.hot_phase is not None:
            events += [(cfg.hot_phase[0] * D, "hot_on"),
                       (cfg.hot_phase[1] * D, "hot_off")]
        if cfg.deadline_phase is not None:
            events += [(cfg.deadline_phase[0] * D, "deadline_on"),
                       (cfg.deadline_phase[1] * D, "deadline_off")]
        events.sort()
        for when, what in events:
            delay = t0 + when - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            logger.info("tenantsim fault: %s at t=%.1fs", what, when)
            try:
                self._apply_fault(what)
            except Exception:
                logger.exception("fault %s failed", what)
                self.report.notes.append(f"fault {what} failed to apply")
        remaining = t0 + D - time.monotonic()
        if remaining > 0:
            time.sleep(remaining)

    def _apply_fault(self, what: str) -> None:
        cfg = self.cfg
        cl = self.cluster
        if what == "storm_on":
            self._storm.set()
        elif what == "storm_off":
            self._storm.clear()
        elif what == "latency_on":
            cl.set_store_latency(cfg.latency_burst_s)
        elif what == "latency_off":
            cl.set_store_latency(0.0)
        elif what == "errors_on":
            cl.set_store_errors(cfg.error_rate)
        elif what == "errors_off":
            cl.set_store_errors(0.0)
        elif what == "kill":
            victim = self._pick_victim()
            if victim is None:
                self.report.notes.append("kill skipped: no safe victim")
                return
            self.report.killed_node = victim.endpoint
            cl.kill_node(victim)
        elif what == "flap":
            owner = cl.samples_owner()
            candidates = [
                n for n in cl.nodes
                if n.alive and n is not owner and n.cluster.shard_set.all_shards()
            ]
            if candidates:
                candidates[0].cluster.pause_heartbeats(cfg.lease_ttl_s * 1.6)
                self.report.notes.append(
                    f"lease flap on {candidates[0].endpoint}"
                )
        elif what == "move":
            from ..engine.metrics_recorder import SAMPLES_TABLE

            moved = cl.migrate_some_shard({SAMPLES_TABLE})
            self.report.notes.append(f"migrated shard {moved}")
        elif what == "hot_on":
            self._resolve_hot_tables()
            self._hot.set()
        elif what == "hot_off":
            self._hot.clear()
        elif what == "deadline_on":
            self._deadline.set()
        elif what == "deadline_off":
            self._deadline.clear()
            # sample the timed_out evidence NOW: the query_stats ring
            # (256 rows) rolls over long before end-of-run collection,
            # but the phase's rows are still in it at phase end
            try:
                eps = cl.alive_endpoints()
                s, out = self._sql(
                    eps[0],
                    "SELECT count(timed_out) AS c FROM "
                    "system.public.query_stats WHERE timed_out = 1 "
                    f"AND timestamp >= {self._t0_ms}",
                    timeout=10,
                )
                if s == 200 and out.get("rows"):
                    self.report.deadline_timed_out_rows = int(
                        out["rows"][0]["c"] or 0
                    )
            except Exception:
                pass

    def _resolve_hot_tables(self) -> None:
        """Pick the skew target: the sim tables co-owned by ONE node (the
        most-loaded-node-to-be). With >= 2 co-owned tables a skew-
        reducing elastic move is possible by construction, so the gate
        may demand one; a fleet whose tables all live on different nodes
        only gates scale-out/in."""
        owners: dict[str, list] = {}
        for j in range(self.cfg.tables):
            name = self._table(j)
            owners.setdefault(self._owner(name), []).append(name)
        _ep, tables = max(owners.items(), key=lambda kv: (len(kv[1]), kv[0]))
        hot = tables[:2]
        self.report.hot_tables = hot
        self.report.elastic_move_expected = (
            bool(self.cfg.elastic) and len(hot) >= 2
        )
        self._hot_refs = [r for r in self._refs if r[1] in hot]
        self.report.notes.append(f"hot tables: {hot}")

    def _pick_victim(self) -> Optional[SimNode]:
        """A node that leads shards but does NOT hold the samples table
        (the SLO evaluator's history must survive the kill — in a real
        fleet the observer would be replicated; the sim kills a worker)."""
        owner = self.cluster.samples_owner()
        for n in self.cluster.nodes:
            if (
                n.alive
                and n is not owner
                and n.cluster.shard_set.all_shards()
            ):
                return n
        return None

    # -- post-run verdicts -------------------------------------------------

    def _settle(self) -> None:
        """Give the plane time to converge — the alert must RESOLVE from
        the database's own evaluation (the fault rate window draining),
        not because the harness declared the fault over."""
        cfg = self.cfg
        deadline = time.monotonic() + cfg.settle_timeout_s
        need_alert_cycle = cfg.error_burst is not None
        need_scale_in = bool(cfg.elastic)

        def scale_in_done(ep) -> bool:
            # scale-in must come from the CONTROLLER's own sustained-
            # quiet decision (the workers stopped; both windows drain)
            before = self._events_before.get("issued", 0)
            s, out = self._sql(
                ep,
                "SELECT attrs FROM system.public.events WHERE "
                f"seq > {before} AND kind = 'elastic_action'",
                timeout=10,
            )
            if s != 200:
                return False
            for row in out.get("rows", []):
                try:
                    if json.loads(row["attrs"]).get("action") == "scale_down":
                        return True
                except Exception:
                    continue
            return False

        def done() -> bool:
            ep = self.cluster.alive_endpoints()[0]
            s2, out2 = self._sql(
                ep,
                "SELECT objective FROM system.public.slo WHERE timestamp > 0",
                timeout=10,
            )
            if not (s2 == 200 and out2.get("rows")):
                return False
            if need_scale_in and not scale_in_done(ep):
                return False
            if not need_alert_cycle:
                return True
            before = self._events_before.get("issued", 0)
            s, out = self._sql(
                ep,
                "SELECT kind FROM system.public.events WHERE "
                f"seq > {before} AND (kind = 'alert_resolved' "
                "OR kind = 'slo_burn' OR kind = 'slo_recovered')",
                timeout=10,
            )
            if s != 200:
                return False
            kinds = [r["kind"] for r in out.get("rows", [])]
            if "alert_resolved" not in kinds:
                return False
            # a burn that happened must also recover before we stop
            # watching (the recovery is half the machinery under test)
            return kinds.count("slo_burn") <= kinds.count("slo_recovered")

        while time.monotonic() < deadline:
            try:
                if done():
                    return
            except Exception:
                pass
            time.sleep(0.5)
        self.report.notes.append("settle timed out (alert may not have resolved)")

    def _collect(self) -> None:
        from ..utils.events import EVENT_STORE

        ep = self.cluster.alive_endpoints()[0]
        before = self._events_before.get("issued", 0)

        # --- SLO verdicts, from the database's own table (timestamp =
        # last evaluation: >= t0 filters idle/stale evaluators out) ---
        s, out = self._sql(
            ep,
            "SELECT objective, state, breaches, burn_fast, burn_slow, "
            "value, bound, target FROM system.public.slo "
            f"WHERE timestamp >= {self._t0_ms}",
            timeout=20,
        )
        if s == 200:
            self.report.slo_rows = out["rows"]
            self.report.slo_active_rows = len(out["rows"])
            for row in out["rows"]:
                if row["objective"] == "cheap_p99":
                    self.report.cheap_objective_breaches = int(row["breaches"])
        # burn/recover transitions from the journal
        s, out = self._sql(
            ep,
            "SELECT kind, attrs FROM system.public.events WHERE "
            f"seq > {before} AND "
            "(kind = 'slo_burn' OR kind = 'slo_recovered')",
            timeout=20,
        )
        if s == 200:
            for row in out["rows"]:
                try:
                    obj = json.loads(row["attrs"]).get("objective", "?")
                except Exception:
                    obj = "?"
                if row["kind"] == "slo_burn":
                    self.report.slo_burned_objectives.append(obj)
                else:
                    self.report.slo_recovered_objectives.append(obj)

        # --- alerts fired AND resolved, from the journal + alerts table ---
        s, out = self._sql(
            ep,
            "SELECT kind, attrs FROM system.public.events WHERE "
            f"seq > {before} AND "
            "(kind = 'alert_fired' OR kind = 'alert_resolved')",
            timeout=20,
        )
        if s == 200:
            for row in out["rows"]:
                try:
                    rule = json.loads(row["attrs"]).get("rule", "?")
                except Exception:
                    rule = "?"
                if row["kind"] == "alert_fired":
                    self.report.alerts_fired.append(rule)
                else:
                    self.report.alerts_resolved.append(rule)

        # --- event journal: contiguous retained window, drops accounted ---
        s, out = self._sql(
            ep, "SELECT seq FROM system.public.events", timeout=20
        )
        if s == 200:
            seqs = sorted(int(r["seq"]) for r in out["rows"])
            self.report.event_count = len(seqs)
            gaps = 0
            for a, b in zip(seqs, seqs[1:]):
                if b != a + 1:
                    gaps += b - a - 1
            self.report.event_seq_gaps = gaps
            stats = EVENT_STORE.stats()
            self.report.event_drops = stats["dropped"]
            # "issued" (not ring-derived last_seq): the pre-run head must
            # survive an earlier test's EVENT_STORE.clear()
            before_last = self._events_before.get("issued", 0)
            before_dropped = self._events_before.get("dropped", 0)
            if seqs:
                # every seq between the pre-run head and the oldest
                # retained entry must be an ACCOUNTED drop
                missing_lead = max(0, seqs[0] - 1 - before_last)
                accounted = stats["dropped"] - before_dropped
                self.report.event_drops_unaccounted = max(
                    0, missing_lead - accounted
                )
            else:
                self.report.event_drops_unaccounted = 0

        # --- elastic control-loop actions, from the journal (the meta's
        # decisions land in the same process-global ring the data nodes
        # serve as system.public.events) ---
        s, out = self._sql(
            ep,
            "SELECT kind, attrs FROM system.public.events WHERE "
            f"seq > {before} AND (kind = 'elastic_action' "
            "OR kind = 'elastic_quarantined')",
            timeout=20,
        )
        if s == 200:
            for row in out["rows"]:
                if row["kind"] == "elastic_quarantined":
                    self.report.elastic_quarantines += 1
                    continue
                try:
                    attrs = json.loads(row["attrs"])
                except Exception:
                    attrs = {}
                action = attrs.get("action", "")
                if action == "scale_up":
                    self.report.elastic_scale_ups += 1
                elif action == "scale_down":
                    self.report.elastic_scale_downs += 1
                elif action == "move":
                    self.report.elastic_moves += 1
                    if attrs.get("prewarmed"):
                        # the cutover target was tailing the manifest
                        # (a replica it already held, or one installed
                        # for the move) — the pre-warmed move proof
                        self.report.elastic_prewarmed_moves += 1
                elif action == "prewarm":
                    self.report.elastic_prewarms += 1

        # --- deadline plane (ISSUE 14), from the database's own tables:
        # the journal carries typed query_timeout events, query_stats
        # carries timed_out rows, and system.public.workload proves the
        # admission slots drained back to baseline (<= the one cheap
        # unit THIS reading query holds while it materializes) ---
        if self.cfg.deadline_phase is not None:
            s, out = self._sql(
                ep,
                "SELECT count(kind) AS c FROM system.public.events WHERE "
                f"seq > {before} AND kind = 'query_timeout'",
                timeout=10,
            )
            if s == 200 and out.get("rows"):
                self.report.deadline_timeout_events = int(
                    out["rows"][0]["c"] or 0
                )
            # "slots back at baseline" is a DRAIN gate, not an instant
            # sample: straggler expensive scans (30s client timeouts)
            # may still be finishing right after the workers stop —
            # poll until the summed in-use units fall to <= 1 (the one
            # cheap unit this reading query holds) or the bound passes,
            # and record the LAST observed value either way
            drain_bound = time.monotonic() + 20.0
            while True:
                s, out = self._sql(
                    ep,
                    "SELECT value FROM system.public.workload "
                    "WHERE name = 'units_in_use'",
                    timeout=10,
                )
                if s == 200 and out.get("rows"):
                    self.report.admission_units_after = int(
                        float(out["rows"][0]["value"] or 0)
                    )
                if (
                    0 <= self.report.admission_units_after <= 1
                    or time.monotonic() >= drain_bound
                ):
                    break
                time.sleep(0.5)

        # --- follower serving (route=follower in query_stats; the ring
        # is process-global in-process, so one node answers for all —
        # informational, the correctness gate is the reference checks) ---
        s, out = self._sql(
            ep,
            "SELECT count(route) AS c FROM system.public.query_stats "
            f"WHERE route = 'follower' AND timestamp >= {self._t0_ms}",
            timeout=10,
        )
        if s == 200 and out["rows"]:
            self.report.follower_served = int(out["rows"][0]["c"] or 0)

        # --- acked-write readback (incl. rows acked by the dead leader) ---
        with self._lock:
            sample = list(self._acked)[-40:]
        missing = 0
        for name, tenant, ts, v in sample:
            ok = False
            for attempt in range(3):
                s, out = self._sql(
                    ep,
                    f"SELECT count(v) AS c FROM {name} "
                    f"WHERE tenant = '{tenant}' AND ts = {ts}",
                    timeout=20,
                )
                if s == 200 and out["rows"] and int(out["rows"][0]["c"]) >= 1:
                    ok = True
                    break
                time.sleep(1.0)
            if not ok:
                missing += 1
        self.report.acked_rows_checked = len(sample)
        self.report.acked_rows_missing = missing

        # --- decision plane (ISSUE 16): every active adaptive loop must
        # have journaled choices, realized outcomes, and a calibration
        # verdict — all read back from the database's own tables ---
        self._collect_decisions(ep)

        # --- profile plane (ISSUE 20): wall-clock attribution rows for
        # every exercised serving plane, untracked fraction bounded ---
        self._collect_profile(ep)

        # --- post-kill recovery: frozen-range reads still agree.
        # "never answered" (still converging / unavailable) and "answered
        # WRONG" are different failures — only a 200 that disagrees is a
        # wrong answer; persistent unavailability fails kill_recovered,
        # its own violation ---
        if self.report.killed_node:
            recovered = True
            for q, _tenant, ref in self._refs[:8]:
                ok = False
                answered_wrong = False
                for attempt in range(10):
                    s, out = self._sql(ep, q, timeout=20)
                    if s == 200:
                        if _rows_agree(out.get("rows", []), ref):
                            ok = True
                            break
                        answered_wrong = True
                    time.sleep(1.0)
                if not ok:
                    recovered = False
                    if answered_wrong:
                        self.report.wrong_answers += 1
                    else:
                        self.report.notes.append(
                            f"post-kill reference never answered: {q[:80]}"
                        )
            self.report.kill_recovered = recovered

    def _livewindow_panel_sql(self, j: int, tenant: int = None) -> str:
        """The eligible open-tail dashboard shape: time_bucket + tenant
        grouping, no ts bound (the tenant filter, when present, pushes
        into the state's group values and does not change the shape
        key)."""
        where = f"WHERE tenant = 't{tenant}' " if tenant is not None else ""
        return (
            f"SELECT time_bucket(ts, '60000ms') AS b, tenant, "
            f"count(v) AS c, sum(v) AS s FROM {self._table(j)} "
            f"{where}GROUP BY time_bucket(ts, '60000ms'), tenant"
        )

    def _drive_livewindow(self, ep: str) -> None:
        """Deterministic promote -> serve -> equivalence -> evict walk
        (ISSUE 18), graded through the decision journal: eligible
        open-tail reads promote the panel shape, fresh rows through the
        ordinary write path advance the ring head past valid_from, a
        state-served read must agree with the HORAEDB_LIVEWINDOW=0 raw
        rescan (ingest is quiesced here, so the kill-switch flip cannot
        race a fold), and explicit evictions resolve every promote
        decision against realized hits."""
        name = self._table(0)
        panel = self._livewindow_panel_sql(0)
        # drop any states promoted by mid-run worker traffic first: their
        # journal entries may already have rolled off the bounded
        # decision ring (admission/kernel_router flood), and a late
        # resolve grades calibration but leaves no resolved row in
        # system.public.decisions — the promote reads below re-issue
        # fresh entries that are still in-ring when the gate SELECTs
        self._evict_livewindow_states(ep)
        for _ in range(4):
            self._sql(ep, panel, timeout=30)
        # fresh rows strictly ABOVE the table max: valid_from was pinned
        # one bucket past the max at promotion, so only buckets beyond
        # it can be state-served
        s, out = self._sql(ep, f"SELECT max(ts) AS m FROM {name}",
                           timeout=20)
        m = None
        if s == 200 and out.get("rows"):
            m = out["rows"][0].get("m")
        # +3 buckets, not +1: a device-served max(ts) is f32-rounded
        # (ulp at epoch-ms magnitude is ~131s, up to 2 buckets either
        # way), and rows below valid_from fold but can never be
        # state-served — the margin keeps the walk above the true max
        base_ms = ((int(m) // 60_000) + 3) * 60_000 if m is not None \
            else int(time.time() * 1000)
        rows = [
            {"tenant": f"t{k % 7}", "host": f"h{k % 3}",
             "v": round(1.0 + 0.5 * k, 4), "ts": base_ms + k * 250}
            for k in range(140)
        ]
        try:
            owner = self._owner(name)
        except Exception:
            owner = ep
        try:
            _http("POST", f"http://{owner}/write",
                  {"table": name, "rows": rows}, timeout=30)
        except Exception:
            pass
        s1, out1 = self._sql(ep, panel, timeout=30)
        prior = os.environ.get("HORAEDB_LIVEWINDOW")
        os.environ["HORAEDB_LIVEWINDOW"] = "0"
        try:
            s2, out2 = self._sql(ep, panel, timeout=30)
        finally:
            if prior is None:
                os.environ.pop("HORAEDB_LIVEWINDOW", None)
            else:
                os.environ["HORAEDB_LIVEWINDOW"] = prior
        if s1 == 200 and s2 == 200:
            def _key(r):
                return (str(r.get("b")), str(r.get("tenant")))

            a = sorted(out1.get("rows", []), key=_key)
            b = sorted(out2.get("rows", []), key=_key)
            with self._lock:
                self.report.livewindow_equiv_checked += 1
                # f32 device partials vs the f64 rescan
                if _rows_agree(a, b, rtol=2e-3):
                    self.report.livewindow_equiv_ok += 1
                else:
                    self.report.wrong_answers += 1
                    self.report.notes.append(
                        "livewindow state answer != raw rescan"
                    )
        # route=livewindow evidence from the database's own ledger
        s, out = self._sql(
            ep,
            "SELECT count(route) AS c FROM system.public.query_stats "
            "WHERE route = 'livewindow'",
            timeout=10,
        )
        if s == 200 and out.get("rows"):
            self.report.livewindow_served = int(out["rows"][0]["c"] or 0)
        # explicit evictions: each resolves its promote decision with
        # realized hits, so the loop's calibration verdict gets graded
        # samples even if the byte budget never forced an eviction
        self._evict_livewindow_states(ep)

    def _evict_livewindow_states(self, ep: str) -> None:
        try:
            s, st = _http("GET", f"http://{ep}/debug/livewindow",
                          timeout=10)
            if s == 200:
                for row in st.get("states", []):
                    _http(
                        "DELETE",
                        f"http://{ep}/debug/livewindow/{row['key']}",
                        timeout=10,
                    )
        except Exception:
            pass

    def _collect_profile(self, ep: str) -> None:
        """Profile-plane standing gate (ISSUE 20), from the database's
        own ``system.public.profile``: every serving plane the sim
        exercised (query/ingest/flush/compaction/rules) must show >= 1
        attribution row, and the untracked fraction of root wall must
        stay under the coverage bound. Compaction is made deterministic
        first: trigger-level one-row flushes of table 0 accumulate the
        L0 runs the background scheduler reacts to."""
        name = self._table(0)
        owner = self._owner(name)
        ts0 = int(time.time() * 1000)
        for k in range(5):
            self._sql(
                ep,
                f"INSERT INTO {name} (tenant, host, v, ts) VALUES "
                f"('profile', 'h0', {float(k)}, {ts0 + k})",
                timeout=10,
            )
            _http(
                "POST", f"http://{owner}/admin/flush?table={name}", {},
                timeout=15,
            )
        # in-process nodes share the global aggregator: drain the fold
        # queue, then poll until the background compaction round (and a
        # rules-eval tick) have landed their rows
        from ..obs.profile import flush as profile_flush

        routes_needed = ("query", "ingest", "flush", "compaction", "rules")
        rows: list = []
        deadline = time.time() + 20.0
        while True:
            profile_flush(5.0)
            s, out = self._sql(
                ep,
                "SELECT path, route, total_ms FROM system.public.profile",
                timeout=10,
            )
            rows = out.get("rows", []) if s == 200 else []
            seen = {r.get("route") for r in rows}
            if all(r in seen for r in routes_needed):
                break
            if time.time() >= deadline:
                break
            time.sleep(0.25)
        counts: dict = {}
        root_ms: dict = {}
        untracked_ms: dict = {}
        for r in rows:
            route = r.get("route", "")
            counts[route] = counts.get(route, 0) + 1
            path = r.get("path", "")
            ms = float(r.get("total_ms") or 0.0)
            if "/" not in path:
                root_ms[route] = root_ms.get(route, 0.0) + ms
            elif path.endswith("/" + "(untracked)"):
                untracked_ms[route] = untracked_ms.get(route, 0.0) + ms
        self.report.profile_route_rows = counts
        total_root = sum(root_ms.values())
        total_untracked = sum(max(0.0, v) for v in untracked_ms.values())
        self.report.profile_untracked_fraction = (
            round(total_untracked / total_root, 4)
            if total_root > 0 else None
        )

    def _collect_decisions(self, ep: str) -> None:
        """Decision-plane gates (ISSUE 16), from the database's own
        ``system.public.decisions`` / ``system.public.calibration``: per
        ACTIVE loop >= 1 resolved decision and a finite calibration
        verdict, and the journal's accounting must reconcile exactly
        (issued == resolved + expired + unresolved per loop — the ring's
        unresolved evictions and TTL expiries are both counted expired,
        so nothing ever goes missing silently)."""
        cfg = self.cfg
        active = ["kernel_router", "admission"]
        if cfg.deadline_phase is not None:
            active.append("deadline")
        if cfg.elastic:
            active.append("elastic")
        if cfg.dtype_auto:
            active.append("layout_tuner")
        if cfg.livewindow:
            active.append("livewindow")
            self._drive_livewindow(ep)
        self.report.decision_active_loops = active

        if cfg.dtype_auto:
            # deterministic tuner activation: two sightings build the
            # cache entry (v bf16-resident — its only observed usage is
            # min/max), then the sum GROWS the usage and forces the
            # promotion: decision recorded at the bf16 drop, resolved at
            # the f32 re-upload inside the same serving call
            for _ in range(3):
                self._sql(ep, self._dtype_minmax_sql(), timeout=20)
            self._sql(ep, self._dtype_sum_sql(), timeout=20)
        # post-run refresh of the expensive dashboard shape, unbudgeted:
        # a full multi-agg scan takes the segment-kernel route (the
        # cohort batcher owns the cheap shapes, so this is what keeps
        # the kernel-router loop exercised in every config), and when a
        # deadline storm ran, its ok completion resolves still-pending
        # shed decisions (graded doomed vs premature against realized
        # cost) — the storm's shape must not dangle unresolved. Two
        # passes: the first pick of a fresh shape has no router timing
        # history (predicted=None, honest but ungradable); the second
        # pick predicts from the first's recorded seconds and GRADES.
        for _ in range(2):
            for j in range(cfg.tables):
                self._sql(
                    ep,
                    f"SELECT tenant, count(v) AS c, sum(v) AS s, "
                    f"min(v) AS mn, max(v) AS mx FROM {self._table(j)} "
                    "GROUP BY tenant",
                    tenant="storm", timeout=30,
                )

        s, out = self._sql(
            ep, "SELECT loop, resolved FROM system.public.decisions",
            timeout=10,
        )
        if s == 200:
            counts: dict = {}
            for r in out.get("rows", []):
                if r.get("resolved"):
                    lp = r.get("loop", "?")
                    counts[lp] = counts.get(lp, 0) + 1
            self.report.decision_resolved_counts = counts

        s, out = self._sql(
            ep,
            "SELECT loop, samples, ewma_abs, issued, resolved, expired, "
            "missed, unresolved FROM system.public.calibration",
            timeout=10,
        )
        if s == 200:
            unaccounted = 0
            for r in out.get("rows", []):
                lp = r.get("loop", "?")
                c = {
                    k: int(r.get(k) or 0)
                    for k in ("issued", "resolved", "expired", "missed",
                              "unresolved")
                }
                self.report.decision_counts[lp] = c
                unaccounted += abs(
                    c["issued"] - c["resolved"] - c["expired"]
                    - c["unresolved"]
                )
                e = r.get("ewma_abs")
                self.report.calibration_verdicts[lp] = bool(
                    int(r.get("samples") or 0) >= 1
                    and e is not None
                    and math.isfinite(float(e))
                )
            self.report.decision_unaccounted = unaccounted


def run_sim(cfg: SimConfig) -> SimReport:
    return TenantSim(cfg).run()


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(prog="tenantsim", description=__doc__)
    p.add_argument("--nodes", type=int, default=3)
    p.add_argument("--tenants", type=int, default=200)
    p.add_argument("--tables", type=int, default=3)
    p.add_argument("--duration", type=float, default=45.0)
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--workers", type=int, default=6)
    p.add_argument("--rows", type=int, default=30_000)
    p.add_argument("--read-replicas", type=int, default=1)
    p.add_argument("--no-kill", action="store_true")
    p.add_argument(
        "--elastic", action="store_true",
        help="run the [cluster.elastic] control loop on the meta and add "
             "the hot-tenant skew phase (gates: scale-out under skew, "
             "route=follower serving, pre-warmed move, scale-in after)",
    )
    p.add_argument(
        "--no-batch", action="store_true",
        help="disable [wlm.batch] cohort batching on the nodes (the "
             "dashboard flood then pays one device dispatch per query)",
    )
    p.add_argument(
        "--no-deadline-storm", action="store_true",
        help="skip the slow-storm-with-tight-deadlines phase (expired "
             "queries answering the typed 504 within budget, admission "
             "slots draining back to baseline)",
    )
    p.add_argument(
        "--no-livewindow", action="store_true",
        help="issue the legacy count(v) open-tail panel instead of the "
             "eligible time_bucket shape (disables the live-window "
             "promote/serve/evict gate)",
    )
    p.add_argument("--json", action="store_true", help="emit the report as JSON")
    args = p.parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    cfg = SimConfig(
        nodes=args.nodes,
        tenants=args.tenants,
        tables=args.tables,
        duration_s=args.duration,
        seed=args.seed,
        workers=args.workers,
        rows_per_table=args.rows,
        read_replicas=0 if args.elastic else args.read_replicas,
        elastic=args.elastic,
        hot_phase=(0.1, 0.45) if args.elastic else None,
        batch=not args.no_batch,
        deadline_phase=(
            None if args.no_deadline_storm or args.elastic else (0.2, 0.45)
        ),
        kill_at=None if args.no_kill else SimConfig.kill_at,
        lease_flap_at=0.72 if args.nodes >= 3 else None,
        shard_move_at=0.8 if args.nodes >= 3 else None,
        settle_timeout_s=40.0 if args.elastic else SimConfig.settle_timeout_s,
        livewindow=not args.no_livewindow,
    )
    report = run_sim(cfg)
    violations = report.violations()
    if args.json:
        # machine mode: the report is the ONLY stdout (violations ride
        # inside it; the exit code conveys pass/fail)
        print(json.dumps(report.to_dict(), indent=1, sort_keys=True))
        return 1 if violations else 0
    d = report.to_dict()
    for k in sorted(d):
        if k not in ("config", "slo_rows"):
            print(f"{k}: {d[k]}")
    print("\nslo verdicts:")
    for row in report.slo_rows:
        print(f"  {row}")
    if violations:
        print("\nVIOLATIONS:")
        for v in violations:
            print(f"  - {v}")
        return 1
    print("\nall acceptance gates passed")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
