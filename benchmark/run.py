#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, in one process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Refuse to start without a TPU that ``peaks.json`` knows -> compile cache ->
the deployment's data from ``--seed`` -> load through the engine's write path
and flush (on-disk WAL + SST under a temporary directory) -> ``create_app`` on
a loopback port -> warm up with the cell's own traffic -> measure for
``--seconds`` -> wait out background compiles -> compare every answer with the
plain reference -> print the result as the last line of standard output.

Everything that belongs to one cell, configuration, traffic mix, statement or
per-layer metric is a file found by its name: ``workloads/``, ``configs/``,
``traffic/``, ``statements/``, ``layer_metrics/`` and ``reducers/``. This file
holds none of their names.

``--rehearse`` runs the same control flow on the CPU at the configuration's
``rehearse`` scale: it says so in its result, reports no device metric, and is
no measurement.
"""

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse
import contextlib
import faulthandler
import http.client
import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile
import threading

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

import numpy as np  # noqa: E402

from compare import worst  # noqa: E402
from tsbs_data import CREATE_TABLE, TAGS, World  # noqa: E402

REQUEST_TIMEOUT_S = 120
SQL = "/sql"
RETRYABLE = (429, 503)  # admission shed, quota: "retry later"


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, found by the name a JSON file gives."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{kind}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def log(**obj) -> None:
    print(json.dumps(obj, default=str), file=sys.stderr, flush=True)


# ---- the cell ---------------------------------------------------------------


class Cell:
    def __init__(self, name: str, rehearse: bool) -> None:
        self.name = name
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            self.benchmark = json.load(f)
        entry = [w for w in self.benchmark["workloads"] if w["name"] == name]
        if not entry:
            raise SystemExit(f"BENCHMARK.json has no workload {name!r}")
        self.chips = entry[0]["chips"]
        self.workload = load_json("workloads", name + ".json")
        self.config = load_json("configs", entry[0]["config"] + ".json")
        if rehearse:
            self.config = {**self.config, **self.config["rehearse"]}
        self.traffic = load_json("traffic", entry[0]["traffic"] + ".json")
        self.limits = self.workload["limits"]
        self.groups = [
            {**g, "module": load_module("statements", g["statement"])}
            for g in self.traffic["groups"]
        ]

    def metrics(self, kind: str) -> list[dict]:
        """The cell's entries of ``end_to_end`` or ``per_layer``."""
        return [m for m in self.benchmark[kind]
                if self.name in m.get("workloads", [self.name])]


# ---- the server, in this process ----------------------------------------------


class Server:
    """``create_app(conn)`` on a loopback port, on its own event-loop thread
    (a copy of ``chip_smoke.py``'s)."""

    def __init__(self, conn) -> None:
        import asyncio

        from aiohttp import web

        from horaedb_tpu.server import create_app

        self._asyncio = asyncio
        self._loop = asyncio.new_event_loop()
        self._runner = web.AppRunner(create_app(conn))
        self._loop.run_until_complete(self._runner.setup())
        site = web.TCPSite(self._runner, "127.0.0.1", 0)
        self._loop.run_until_complete(site.start())
        self.port = self._runner.addresses[0][1]
        self._thread = threading.Thread(
            target=self._loop.run_forever, name="bench-http", daemon=True
        )
        self._thread.start()

    def connect(self) -> http.client.HTTPConnection:
        return http.client.HTTPConnection("127.0.0.1", self.port, timeout=REQUEST_TIMEOUT_S)

    def get(self, path: str) -> bytes:
        conn = self.connect()
        try:
            conn.request("GET", path)
            return conn.getresponse().read()
        finally:
            conn.close()

    def close(self) -> None:
        self._asyncio.run_coroutine_threadsafe(
            self._runner.cleanup(), self._loop
        ).result(timeout=60)
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=60)
        self._loop.close()


def load(world: World, data_dir: str):
    """The deployment's rows through the engine's write path, then one flush:
    on-disk WAL + SST under ``data_dir``. -> the open connection."""
    import horaedb_tpu
    from horaedb_tpu.common_types import RowGroup
    from horaedb_tpu.common_types.schema import compute_tsid

    conn = horaedb_tpu.connect(data_dir)
    conn.execute(CREATE_TABLE.format(segment_duration=world.config["segment_duration"]))
    table = conn.catalog.open("cpu")
    columns = world.load_columns()
    columns["tsid"] = compute_tsid([columns[t] for t in TAGS])
    table.write(RowGroup(table.schema, columns))
    table.flush()
    return conn


def serve(conn) -> Server:
    server = Server(conn)
    deadline = time.monotonic() + 120
    while not json.loads(server.get("/health?ready=1")).get("ready"):
        if time.monotonic() > deadline:
            raise RuntimeError("the server never became ready")
        time.sleep(0.1)
    return server


def parse_metrics(text: str) -> dict[str, float]:
    """Prometheus text -> {``name{labels}``: value}."""
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            key, _, value = line.rpartition(" ")
            try:
                out[key] = float(value)
            except ValueError:
                pass
    return out


class Evidence:
    """The program's counters and spans around the window, the clients'
    records of it and (traced runs) the reduced trace: what the per-layer
    readers read."""

    def __init__(self, cell: Cell, world: World, peak: dict | None) -> None:
        self.cell, self.world, self.peak = cell, world, peak
        self.before: dict = {}
        self.after: dict = {}
        self.records: list[dict] = []  # the window's requests
        self.window_s = 0.0
        self.query_stats: list[dict] = []
        self.device_table: list[dict] = []
        self.trace: dict | None = None
        self.memory_peak_bytes = 0
        self._values: dict[str, float | None] = {}

    @staticmethod
    def snapshot(server: Server) -> dict:
        return {
            "metrics": parse_metrics(server.get("/metrics").decode()),
            "profile": json.loads(server.get("/debug/profile"))["profile"],
        }

    def counter(self, key: str) -> float:
        """How far a counter of ``/metrics`` moved over the window."""
        return self.after["metrics"].get(key, 0.0) - self.before["metrics"].get(key, 0.0)

    def counters(self, prefix: str) -> dict[str, float]:
        keys = [k for k in self.after["metrics"] if k.startswith(prefix)]
        return {k: self.counter(k) for k in keys}

    def span(self, path: str) -> tuple[float, float]:
        """-> (inclusive ms, count) that the span at ``path`` gained over the
        window, summed over routes and shapes."""
        def total(rows):
            hit = [r for r in rows if r["path"] == path]
            return sum(r["total_ms"] for r in hit), sum(r["count"] for r in hit)
        (ms1, n1), (ms0, n0) = total(self.after["profile"]), total(self.before["profile"])
        return ms1 - ms0, n1 - n0

    def completed(self, endpoint: str) -> list[dict]:
        """The window's requests to one endpoint: every one sent in it."""
        return [r for r in self.records if r["endpoint"] == endpoint]

    def metric(self, name: str):
        """A per-layer metric's value by its name, or None where its reader
        finds nothing to read."""
        if name not in self._values:
            spec = load_json("layer_metrics", name + ".json")
            reader = load_module("reducers", spec["reducer"])
            self._values[name] = reader.read(self, spec.get("args", {}))
        return self._values[name]


# ---- traffic: one general generator ---------------------------------------------


class Clients:
    """The traffic file's groups as closed-loop client threads. Each client
    draws a request from its statement, sends it, reads the body whole and
    keeps it; nothing is parsed here."""

    def __init__(self, cell: Cell, world: World, server: Server, phase: int,
                 annotate: bool = False) -> None:
        self.cell, self.world, self.server = cell, world, server
        self.records: list[dict] = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._annotate = annotate
        self.threads = []
        for gi, group in enumerate(cell.groups):
            for ci in range(group["clients"]):
                rng = np.random.default_rng([world.seed, phase, gi, ci])
                t = threading.Thread(target=self._loop, args=(group, rng),
                                     name=f"client-{gi}-{ci}", daemon=True)
                self.threads.append(t)

    def start(self) -> None:
        for t in self.threads:
            t.start()

    def stop(self) -> None:
        """No new request; those in flight are waited for."""
        self._stop.set()
        for t in self.threads:
            t.join(timeout=REQUEST_TIMEOUT_S + 10)
            if t.is_alive():
                raise RuntimeError(f"{t.name} never came back")

    def _loop(self, group: dict, rng) -> None:
        module, params = group["module"], group.get("params", {})
        endpoint = module.ENDPOINT
        headers = {"Content-Type": "application/json"}
        conn = self.server.connect()
        span = contextlib.nullcontext
        if self._annotate:  # traced runs: the request on the trace's clock
            from jax.profiler import TraceAnnotation as span
        while not self._stop.is_set():
            body, ticket = module.draw(rng, self.world, params)
            data = json.dumps(body).encode()
            rec = {"endpoint": endpoint, "group": group, "ticket": ticket,
                   "sql": body.get("query"), "wall_sent": time.time(),
                   "sent": time.perf_counter()}
            try:
                with span("client:" + endpoint):
                    conn.request("POST", endpoint, data, headers)
                    resp = conn.getresponse()
                    rec["body"] = resp.read()
                rec["status"] = resp.status
            except (OSError, http.client.HTTPException) as e:
                rec["status"], rec["body"] = 0, repr(e).encode()
                conn.close()
                conn = self.server.connect()
            rec["done"] = time.perf_counter()
            rec["wall_done"] = time.time()
            with self._lock:
                self.records.append(rec)
        conn.close()


def warm_up(cell: Cell, world: World, server: Server) -> dict:
    """The cell's own traffic, from another stream of the seed, until the scan
    cache is resident, nothing has compiled for ``quiet_requests`` responses
    in a row and no background compile is running."""
    from horaedb_tpu.ops import merge_dedup

    spec = cell.traffic["warmup"]
    compiled_key = 'horaedb_events_total{kind="kernel_compile"}'
    clients = Clients(cell, world, server, phase=0)
    t0 = time.perf_counter()
    clients.start()
    compiles, shed, quiet_since, resident = -1.0, 0, 0, 0
    try:
        while True:
            time.sleep(0.25)
            done = len(clients.records)
            now = parse_metrics(server.get("/metrics").decode()).get(compiled_key, 0.0)
            refused = sum(r["status"] in RETRYABLE for r in list(clients.records))
            if now != compiles or refused != shed or merge_dedup._compiling:
                compiles, shed, quiet_since = now, refused, done
            if done >= spec["min_requests"] and done - quiet_since >= spec["quiet_requests"]:
                resident = sum(r["bytes"] for r in resident_columns(server))
                if resident > 0:
                    break
            if time.perf_counter() - t0 > spec["max_seconds"]:
                raise RuntimeError(
                    f"warm-up did not settle in {spec['max_seconds']} s: {done} responses, "
                    f"{compiles} compiles, compiling {merge_dedup._compiling}"
                )
    finally:
        clients.stop()
    # while a first dispatch compiles, admission sheds the clients queued
    # behind it with a retryable 503: that is set-up, not a failure
    bad = [r for r in clients.records if r["status"] != 200 and r["status"] not in RETRYABLE]
    if bad:
        raise RuntimeError(f"warm-up: {len(bad)} requests failed, first {bad[0]['body'][:300]}")
    return {"requests": len(clients.records), "compiles": compiles, "shed": shed,
            "resident_bytes": resident, "seconds": time.perf_counter() - t0}


def resident_columns(server: Server) -> list[dict]:
    """The column rows of ``system.public.device``, as ``/debug/device`` serves
    them (no admission slot needed)."""
    inventory = json.loads(server.get("/debug/device"))["inventory"]
    return [r for r in inventory if r.get("component") == "column"]


# ---- what the window's answers are held to ---------------------------------------


def judge(evidence: Evidence) -> tuple[dict, int]:
    """Every response of the window against the plain reference. -> (the
    numbers compared, how many requests failed). Marks each record ``ok``.

    A body that is byte for byte one already judged for the same group and
    ticket takes that body's numbers: equal bytes cannot compare differently,
    so only a distinct body is parsed and compared, and what follows the
    window does not grow with the rate the window measured."""
    cell, world = evidence.cell, evidence.world
    t0 = time.perf_counter()
    judged: dict[tuple, list[tuple]] = {}  # (group, ticket) -> [(the bytes, their numbers)]
    numbers = []
    failed = 0
    for rec in evidence.records:
        got = None
        if rec["status"] == 200:
            seen = judged.setdefault((id(rec["group"]), rec["ticket"]), [])
            for raw, got in seen:
                if raw == rec["body"]:
                    break
            else:
                got = compare_body(world, rec["group"], rec["ticket"], rec["body"])
                seen.append((rec["body"], got))
        rec["body"] = None  # the bodies are the run's largest allocation
        rec["ok"] = got is not None and all(
            v <= cell.limits[k] for k, v in got.items() if k in cell.limits
        )
        numbers.append({"error_responses": 1} if got is None else got)
        failed += not rec["ok"]
    log(phase="judge", answers=len(evidence.records),
        distinct=sum(map(len, judged.values())), seconds=time.perf_counter() - t0)
    numbers.append({"error_responses": 0, "device_served_compared": device_served(evidence)})
    return worst(numbers), failed


def compare_body(world: World, group: dict, ticket, raw: bytes) -> dict | None:
    """One body against the plain reference's answer for its ticket. -> the
    numbers compared, or None where the body is no JSON."""
    module, params = group["module"], group.get("params", {})
    try:
        body = json.loads(raw)
    except ValueError:
        return None
    want = module.reference(world, params, ticket)
    return module.compare(body.get("rows", body), want, params)


def device_served(evidence: Evidence) -> int:
    """How many of the compared /sql responses ``query_stats`` shows a device
    dispatch served: a ring row is paired with the one response that has its
    statement text (the ring keeps 200 characters) and was in flight when the
    row was stamped."""
    by_text: dict[str, list[dict]] = {}
    for rec in evidence.records:
        if rec["endpoint"] == SQL and rec["status"] == 200:
            by_text.setdefault(rec["sql"][:200], []).append(rec)
    taken: set[int] = set()
    for row in evidence.query_stats:
        if not row.get("device_dispatches"):
            continue
        stamp = row["timestamp"] / 1000.0
        hits = [r for r in by_text.get(row["sql"], [])
                if r["wall_sent"] - 0.002 <= stamp <= r["wall_done"] + 0.002
                and id(r) not in taken]
        if hits:  # twins in flight together: the one that ended nearest the stamp
            taken.add(id(min(hits, key=lambda r: abs(r["wall_done"] - stamp))))
    return len(taken)


def end_to_end(cell: Cell, evidence: Evidence, setup_s: float) -> dict:
    """The cell's end-to-end metrics, each as ``end_to_end/<name>.json``
    defines it: a statistic over every request to one endpoint that was sent
    in the window and answered correctly, and over all of the window's time."""
    values = {}
    for m in cell.metrics("end_to_end"):
        spec = load_json("end_to_end", m["name"] + ".json")
        if spec["statistic"] == "setup":
            values[m["name"]] = setup_s
            continue
        lat = sorted((r["done"] - r["sent"]) * 1000.0
                     for r in evidence.completed(spec["endpoint"]) if r["ok"])
        if not lat:
            continue
        if spec["statistic"] == "rate":
            values[m["name"]] = len(lat) / evidence.window_s
        else:
            values[m["name"]] = percentile(lat, spec["statistic"])
    return values


def host_state() -> dict:
    """What the host gives this process right now, for whoever reads the
    result: a one-chip machine shares its host, and the cells the host bounds
    swing with it. Not a metric."""
    a, b = np.ones(1 << 23), np.empty(1 << 23)  # 64 MiB each way
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        np.copyto(b, a)
        best = min(best, time.perf_counter() - t0)
    return {"memcpy_gb_s": 2 * a.nbytes / best / 1e9, "loadavg_1m": os.getloadavg()[0],
            "cpus": len(os.sched_getaffinity(0))}


def routes_seen(evidence: Evidence) -> dict:
    """route/kernel of the last queries the program's ring keeps."""
    seen: dict[str, int] = {}
    for row in evidence.query_stats:
        key = f"{row.get('route')}/{row.get('kernel')}"
        seen[key] = seen.get(key, 0) + 1
    return seen


def percentile(ordered: list[float], share: float) -> float:
    """The median as ``statistics.median`` has it; above it, the nearest rank."""
    if share == 0.5:
        return statistics.median(ordered)
    return ordered[min(len(ordered) - 1, int(share * len(ordered)))]


def latency_summary(evidence: Evidence) -> dict:
    """For the reader of the result line: n, min, median, p90, p95, p99 and max
    in ms per endpoint, over the window's requests."""
    out = {}
    for endpoint in sorted({r["endpoint"] for r in evidence.records}):
        lat = sorted((r["done"] - r["sent"]) * 1000.0 for r in evidence.completed(endpoint))
        if lat:
            out[endpoint] = {"n": len(lat), "min": lat[0], "p50": percentile(lat, 0.5),
                             "p90": percentile(lat, 0.90), "p95": percentile(lat, 0.95),
                             "p99": percentile(lat, 0.99), "max": lat[-1]}
    return out


# ---- one run ---------------------------------------------------------------------


def check_device(cell: Cell, peaks: dict, rehearse: bool) -> dict | None:
    """-> the device as the result names it, or None where this is no machine
    to measure on: no TPU, one that ``peaks.json`` does not know, or
    fewer chips than the cell asks for."""
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if not rehearse:
        if device["platform"] != "tpu":
            log(error=f"JAX found no TPU (platform {device['platform']!r})")
            return None
        if device["kind"] not in peaks:
            log(error=f"peaks.json has no entry for {device['kind']!r}")
            return None
        if device["count"] < cell.chips:
            log(error=f"the cell needs {cell.chips} chips, JAX reports {device['count']}")
            return None
    return device


def measure(cell: Cell, evidence: Evidence, seconds: float, trace_dir: str | None,
            phases: dict) -> tuple[float, dict]:
    """Load, serve, warm up, run the window and gather what the program says
    about it; close the program. -> (setup_s, the warm-up's record).
    ``trace_dir``: trace the window into it."""
    import jax

    from horaedb_tpu.ops import merge_dedup

    world = evidence.world
    data_dir = tempfile.mkdtemp(prefix="horaedb_bench_")
    conn = server = None
    try:
        t0 = time.perf_counter()
        conn = load(world, data_dir)
        phases["load"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        server = serve(conn)
        warm = warm_up(cell, world, server)
        phases["warm_up"] = time.perf_counter() - t0
        log(phase="set-up", seconds=phases, warm_up=warm)

        phases["host_before"] = host_state()
        evidence.before = evidence.snapshot(server)
        clients = Clients(cell, world, server, phase=1, annotate=trace_dir is not None)
        window = contextlib.ExitStack()  # the window on the trace's clock
        if trace_dir is not None:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            window.enter_context(jax.profiler.TraceAnnotation("benchmark:window"))
        try:
            opened = time.perf_counter()
            clients.start()
            time.sleep(seconds)
            # no new request after ``seconds``; those sent are the window's
            # work, and the window lasts until the last of them has answered
            clients.stop()
            window.close()
        finally:
            if trace_dir is not None:
                jax.profiler.stop_trace()
        evidence.records = clients.records
        evidence.window_s = max(r["done"] for r in evidence.records) - opened
        phases["host_after"] = host_state()
        evidence.memory_peak_bytes = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()
        )
        evidence.query_stats = json.loads(server.get("/debug/query_stats"))["queries"]
        evidence.after = evidence.snapshot(server)
        evidence.device_table = resident_columns(server)

        # a background compile must not outlive the process, nor a close
        t0 = time.perf_counter()
        while merge_dedup._compiling and time.perf_counter() - t0 < 600:
            time.sleep(0.5)
        phases["background_compiles"] = time.perf_counter() - t0
    finally:
        if server is not None:
            server.close()
        if conn is not None:
            conn.close()
        shutil.rmtree(data_dir, ignore_errors=True)
    return opened - T_START, warm


def hold_to_limits(cell: Cell, compared: dict) -> dict:
    """Each number compared beside its limit. A number the cell limits and the
    run did not produce does not hold."""
    verdicts = {}
    for name, limit in cell.limits.items():
        value = compared.get(name)
        at_least = name in cell.workload.get("at_least", [])
        ok = value is not None and (value >= limit if at_least else value <= limit)
        verdicts[name] = {"value": value, "limit": limit,
                          "holds": "at least" if at_least else "at most", "ok": ok}
    return verdicts


def read_trace(trace_dir: str) -> dict:
    import glob

    from reduce_trace import load_xplane, reduce

    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    return reduce(load_xplane(found[0]))


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="CPU, tiny scale: control flow only, never a measurement")
    args = p.parse_args(argv)
    cell = Cell(args.workload, args.rehearse)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    peaks = load_json("peaks.json")
    device = check_device(cell, peaks, args.rehearse)
    if device is None:
        return 3

    import jax

    from horaedb_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # every program goes to the cache, the quick ones too: set-up is the same
    # work from the second run on
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    phases = {"import": time.perf_counter() - T_START}
    log(phase="device", device=device, compile_cache=cache_dir)

    t0 = time.perf_counter()
    world = World(cell.config, abs(args.seed))
    phases["generate"] = time.perf_counter() - t0
    evidence = Evidence(cell, world, peaks.get(device["kind"]))
    trace_dir = tempfile.mkdtemp(prefix="horaedb_trace_") if args.trace else None
    try:
        setup_s, warm = measure(cell, evidence, args.seconds, trace_dir, phases)
        device["memory_peak_bytes"] = evidence.memory_peak_bytes

        # the program's state is gone; now the plain reference
        t0 = time.perf_counter()
        compared, failed = judge(evidence)
        phases["compare"] = time.perf_counter() - t0
        verdicts = hold_to_limits(cell, compared)

        result = {"correct": all(v["ok"] for v in verdicts.values()),
                  "attempted": len(evidence.records), "failed": failed}
        if args.trace:
            kind = "per_layer"
            t0 = time.perf_counter()
            if not args.rehearse:
                evidence.trace = read_trace(trace_dir)
                device["busy_s"] = evidence.trace["busy_s"]
                device["window_s"] = evidence.trace["window_s"]
                result["breakdown"] = {"device_ops": evidence.trace["device_ops"],
                                       "idle_gaps": evidence.trace["idle_gaps"]}
            values = {m["name"]: evidence.metric(m["name"]) for m in cell.metrics(kind)}
            phases["reduce"] = time.perf_counter() - t0
        else:
            kind = "end_to_end"
            values = end_to_end(cell, evidence, setup_s)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    result["metrics"] = {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in cell.metrics(kind) if values.get(m["name"]) is not None
    }
    result["device"] = device
    if args.rehearse:
        result["rehearsal"] = "CPU at the configuration's rehearse scale: no measurement"
    result["seconds"] = phases
    result["latency_ms"] = latency_summary(evidence)
    result["routes"] = routes_seen(evidence)
    result["requests"] = {
        "window": len(evidence.records),
        "window_s": evidence.window_s,
        "warm_up": warm["requests"],
    }
    result["compared"] = verdicts  # last, as the last lines of standard error
    log(phase="run", seconds=time.perf_counter() - T_START)  # beside the driver's limit on a run
    log(compared=verdicts)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    faulthandler.dump_traceback_later(1150, exit=True)  # a hang must not hold the chip
    sys.exit(main())
