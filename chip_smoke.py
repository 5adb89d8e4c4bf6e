"""Chip smoke: the served path on one TPU chip, at 4000 hosts x 1 h of TSBS
cpu-only data (1,440,000 rows x 10 metrics).

    python chip_smoke.py            one chip: load, serve S1-S4 + /write over HTTP
    python chip_smoke.py --mesh     four chips: load, then S1 and S4 sharded

One process: engine write path -> flush -> scan cache on the device ->
``/sql`` and ``/write`` over a loopback HTTP server in this same process.
Every repeat's rows are compared with the numpy host executor. Exits
non-zero when JAX finds no TPU or any check fails. Every line printed is
one JSON object; the last one is ``{"ok": true, "device": {...}}``.

``--allow-cpu`` is for rehearsal in a sandbox without a chip (typically
with ``--scale 100``): the run then reports the platform it ran on.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import faulthandler
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import urllib.request

import numpy as np

REPEATS = 5
# A hang must not hold the chip: past this many seconds every thread's
# stack goes to stderr and the process exits non-zero.
WATCHDOG_S = 1100
SPAN_MS = 3_600_000  # one hour of data
WRITE_BATCHES = 2
WRITE_BATCH_ROWS = 500
WRITE_HOSTS = 50  # x 10 ticks = 500 rows per batch
S4_SQL = (
    "SELECT hostname, usage_user, ts FROM cpu WHERE hostname = 'host_7' "
    "ORDER BY ts DESC LIMIT 100"
)


def emit(**obj) -> None:
    print(json.dumps(obj, default=str), flush=True)


class Checks:
    """Failed checks are printed as they happen and fail the run at the
    end — one chip run reports every phase, none ends in exit 0."""

    def __init__(self) -> None:
        self.failed: list[str] = []

    def require(self, ok: bool, what: str) -> bool:
        if not ok:
            self.failed.append(what)
            emit(check="FAILED", what=what)
        return bool(ok)


class Server:
    """``create_app(conn)`` on a loopback port, on its own event-loop
    thread in this process."""

    def __init__(self, conn) -> None:
        from aiohttp import web

        from horaedb_tpu.server import create_app

        self._loop = asyncio.new_event_loop()
        self._runner = web.AppRunner(create_app(conn))
        self._loop.run_until_complete(self._runner.setup())
        site = web.TCPSite(self._runner, "127.0.0.1", 0)
        self._loop.run_until_complete(site.start())
        self.port = self._runner.addresses[0][1]
        self._thread = threading.Thread(
            target=self._loop.run_forever, name="smoke-http", daemon=True
        )
        self._thread.start()

    def request(self, method: str, path: str, body=None):
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{path}", data=data, method=method,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=900) as resp:
            return json.loads(resp.read())

    def sql(self, query: str) -> list[dict]:
        return self.request("POST", "/sql", {"query": query})["rows"]

    def close(self) -> None:
        asyncio.run_coroutine_threadsafe(
            self._runner.cleanup(), self._loop
        ).result(timeout=60)
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=60)
        self._loop.close()


def _rows_agree(a: list, b: list, rtol: float = 1e-3, atol: float = 1e-3) -> bool:
    if len(a) != len(b):
        return False

    # Row order is unspecified without ORDER BY; canonicalize before the
    # pairwise numeric comparison. Sort by the exact-typed fields (group
    # keys) first — float aggregates differ slightly between paths and
    # must not drive the pairing.
    def key(row):
        exact = tuple(
            (k, v) for k, v in sorted(row.items()) if not isinstance(v, float)
        )
        approx = tuple(
            (k, round(v, 4)) for k, v in sorted(row.items()) if isinstance(v, float)
        )
        return (exact, approx)

    a = sorted(a, key=key)
    b = sorted(b, key=key)
    for ra, rb in zip(a, b):
        if set(ra) != set(rb):
            return False
        for k in ra:
            va, vb = ra[k], rb[k]
            if isinstance(va, float) or isinstance(vb, float):
                if not np.isclose(va, vb, rtol=rtol, atol=atol, equal_nan=True):
                    return False
            elif va != vb:
                return False
    return True


@contextlib.contextmanager
def host_executor(ex):
    """Force the numpy host executor — the plain reference. Aggregates
    lose both device paths; raw reads take the existing per-call pin."""
    orig_cap, orig_cached = ex._device_capable, ex._try_cached_agg
    ex._device_capable = lambda plan, rows: False
    ex._try_cached_agg = lambda plan, table, m: None
    os.environ["HORAEDB_RAW_DEVICE"] = "0"
    try:
        yield
    finally:
        ex._device_capable, ex._try_cached_agg = orig_cap, orig_cached
        del os.environ["HORAEDB_RAW_DEVICE"]


@contextlib.contextmanager
def device_first(ex):
    """The existing pin that turns the learned host probes off. Raw reads
    take it per call; for aggregates the executor resolves it once and
    keeps the answer, so make it resolve again on the way in and out."""
    prior = os.environ.get("HORAEDB_ADAPTIVE_PATH")
    os.environ["HORAEDB_ADAPTIVE_PATH"] = "0"
    ex._adaptive = None
    try:
        yield
    finally:
        if prior is None:
            del os.environ["HORAEDB_ADAPTIVE_PATH"]
        else:
            os.environ["HORAEDB_ADAPTIVE_PATH"] = prior
        ex._adaptive = None


def run_statement(server, ex, checks, name, sql, device_path, repeats=REPEATS):
    """``repeats`` default-routed serves over HTTP + one host-executor
    serve; -> the per-repeat records."""
    reps, answers = [], []

    def serve(**note) -> dict:
        t0 = time.perf_counter()
        answers.append(server.sql(sql))
        m = ex.last_metrics
        reps.append({
            "path": m.get("path"), "route": m.get("route"),
            "kernel": m.get("kernel") or m.get("raw_kernel"),
            "cache": m.get("cache"), "mesh_devices": m.get("mesh_devices"),
            "rows": len(answers[-1]),
            "seconds": round(time.perf_counter() - t0, 4), **note,
        })
        return m

    for _ in range(repeats):
        serve()
    served = any(r["path"] == device_path for r in reps)
    if not served:
        emit(statement=name, finding=f"never served by {device_path} in "
             f"{repeats} default-routed repeats; retrying device-first",
             paths=[r["path"] for r in reps])
        with device_first(ex):
            # a router that still holds this shape's host verdict is
            # bypassed by the pin: routing is off, device goes first
            m = serve(pinned="HORAEDB_ADAPTIVE_PATH=0")
        served = checks.require(
            m.get("path") == device_path,
            f"{name}: bounced off {device_path} even device-first "
            f"(served by {m.get('path')}; metrics {m})",
        )
    with host_executor(ex):
        reference = server.sql(sql)
        ref_path = ex.last_metrics.get("path")
    checks.require(ref_path == "host", f"{name}: reference ran on {ref_path}")
    checks.require(len(reference) > 0, f"{name}: reference returned no rows")
    agree = [_rows_agree(a, reference) for a in answers]
    bad = [i for i, a in enumerate(agree) if not a]
    if not checks.require(
        not bad, f"{name}: repeats {bad} disagree with the host executor"
    ):
        emit(statement=name, disagreeing_repeat=bad[0], path=reps[bad[0]]["path"],
             got=answers[bad[0]][:3], reference=reference[:3])
    # the ledger's view of the same serves (sql is stored truncated)
    ledger = [
        r for r in server.sql(
            "SELECT sql, route, kernel, device_dispatches, compile_hit "
            "FROM system.public.query_stats"
        ) if r["sql"] == sql[:200]
    ]
    dev_rows = [r for r in ledger if r["route"] == device_path]
    checks.require(
        any((r["device_dispatches"] or 0) > 0 for r in dev_rows),
        f"{name}: no query_stats row with route={device_path} and "
        "device_dispatches > 0",
    )
    emit(statement=name, device_path=device_path, device_served=served,
         repeats=reps, reference_rows=len(reference), agree=agree,
         query_stats=[
             {k: r[k] for k in ("route", "kernel", "device_dispatches", "compile_hit")}
             for r in ledger
         ])
    return reps


def write_phase(server, ex, checks, seed: int) -> None:
    """Two acknowledged /write batches above the table's maximum
    timestamp, then read every row back."""
    from horaedb_tpu.tools import tsbs

    rng = np.random.default_rng(seed + 1)
    ticks = WRITE_BATCH_ROWS // WRITE_HOSTS
    sent = []
    for b in range(WRITE_BATCHES):
        rows = []
        for tick in range(ticks):
            ts = SPAN_MS + (b * ticks + tick) * tsbs.INTERVAL_MS
            for h in range(WRITE_HOSTS):
                region = tsbs.REGIONS[h % len(tsbs.REGIONS)]
                row = {
                    "hostname": f"host_{h}", "region": region,
                    "datacenter": f"{region}{(h // len(tsbs.REGIONS)) % 3}",
                    "ts": ts,
                }
                for f in tsbs.CPU_FIELDS:
                    row[f] = round(float(rng.uniform(0, 100)), 3)
                rows.append(row)
        ack = server.request("POST", "/write", {"table": "cpu", "rows": rows})
        checks.require(
            ack == {"affected_rows": WRITE_BATCH_ROWS},
            f"/write batch {b} acknowledged {ack}",
        )
        sent.extend(rows)
    hi = 2 * SPAN_MS
    count = server.sql(
        f"SELECT count(*) AS c FROM cpu WHERE ts >= {SPAN_MS} AND ts < {hi}"
    )
    checks.require(
        count == [{"c": len(sent)}],
        f"count(*) over the written range is {count}, sent {len(sent)}",
    )
    # S1 widened to two hours covers the new rows; its new minutes must
    # equal the maxima of what was sent, and the whole answer the host's
    wide = tsbs.single_groupby(5, 8, 2).sql
    got = server.sql(wide)
    path = ex.last_metrics.get("path")
    with device_first(ex):
        # the router may hold a host verdict for this shape by now; the
        # cached columns + memtable delta fold must be right as well
        got_device = server.sql(wide)
    m = dict(ex.last_metrics)
    checks.require(
        m.get("path") == "device-cached" and m.get("cache") == "hit+delta",
        f"widened S1 device-first was served by {m.get('path')} "
        f"(cache {m.get('cache')})",
    )
    with host_executor(ex):
        reference = server.sql(wide)
    checks.require(_rows_agree(got, reference), "widened S1 disagrees with the host executor")
    checks.require(_rows_agree(got_device, reference),
                   "widened S1 over cache + delta disagrees with the host executor")
    expect: dict[int, dict] = {}
    for r in sent:
        if int(r["hostname"].split("_")[1]) >= 8:
            continue
        slot = expect.setdefault(r["ts"] // 60_000 * 60_000, {})
        for f in tsbs.CPU_FIELDS[:5]:
            slot[f"max_{f}"] = max(slot.get(f"max_{f}", -1.0), r[f])
    new = {int(r["minute"]): r for r in got if int(r["minute"]) >= SPAN_MS}
    ok = set(new) == set(expect) and all(
        np.isclose(new[m][k], v, rtol=1e-5, atol=1e-3)
        for m, slot in expect.items() for k, v in slot.items()
    )
    checks.require(ok, "widened S1's new minutes differ from the rows sent")
    emit(phase="write", batches=WRITE_BATCHES, rows_acknowledged=len(sent),
         rows_read_back=count[0]["c"], widened_s1_path=path,
         widened_s1_device_first={"path": m.get("path"), "cache": m.get("cache"),
                                  "delta_rows": m.get("delta_rows")},
         widened_s1_minutes=len(got), new_minutes=sorted(new))


def residency(server, conn, checks, devices) -> None:
    rows = server.sql(
        "SELECT column_name, encoding, bytes, logical_rows FROM "
        "system.public.device WHERE component = 'column'"
    )
    total = sum(r["bytes"] for r in rows)
    cache_bytes = conn.interpreters.executor.scan_cache.occupancy_bytes()["column"]
    checks.require(total > 0, "system.public.device reports no resident bytes")
    checks.require(
        total == cache_bytes,
        f"system.public.device sums to {total}, the cache's device_bytes to {cache_bytes}",
    )
    stats = devices[0].memory_stats() or {}
    emit(phase="residency", resident_bytes=total, cache_device_bytes=cache_bytes,
         columns=rows,
         memory_stats={k: stats.get(k) for k in
                       ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")})


def mesh_residency(ex, checks, devices) -> None:
    """Bytes of the cache's row arrays on each device, and the valid rows
    among them (the gauge ``horaedb_scan_cache_shard_rows``: bytes alone
    pass on a layout whose last blocks are padding): every device holds its
    share of the rows, none holds an array whole."""
    from horaedb_tpu.utils.metrics import REGISTRY

    per_device = {str(d): 0 for d in devices}
    whole = []
    with ex.scan_cache._lock:
        entries = list(ex.scan_cache._entries.values())
    for e in entries:
        rows = [
            int(REGISTRY.gauge(
                "horaedb_scan_cache_shard_rows",
                labels={"table": e.table_name, "shard": str(i)},
            ).value)
            for i in range(len(devices))
        ]
        emit(phase="mesh_residency", table=e.table_name, valid_rows=rows)
        checks.require(
            sum(rows) == e.n_valid and max(rows) - min(rows) <= 1,
            f"{e.table_name}: {e.n_valid} rows lie {rows} over the devices",
        )
        arrays = [e.series_codes_dev, e.ts_rel_dev, *e.value_cols_dev.values()]
        for a in arrays:  # a sharded entry keeps every column raw
            for sh in a.addressable_shards:
                per_device[str(sh.device)] += sh.data.nbytes
                if sh.data.shape == a.shape:
                    whole.append(str(sh.device))
    emit(phase="mesh_residency", bytes_per_device=per_device)
    checks.require(
        all(v > 0 for v in per_device.values()),
        f"a device holds nothing: {per_device}",
    )
    checks.require(not whole, f"an array sits whole on {sorted(set(whole))}")


def compile_seconds(server) -> dict:
    events = server.request(
        "GET", "/debug/events?kind=kernel_compile&limit=1000"
    )["events"]
    secs = sum(float(e.get("attrs", {}).get("wall_ms", 0.0)) for e in events) / 1000.0
    block = server.request("GET", "/debug/device")["compile"]
    return {"first_dispatch_seconds": round(secs, 3),
            "first_dispatches": len(events), "by_kernel": block}


def drain_background_compiles(checks, timeout_s: float = 600.0) -> None:
    """The merge read starts its sort kernel's compile on a daemon thread
    and serves from the host meanwhile. Exiting under a compile in flight
    is how a process ends badly on a chip, so wait it out and say so."""
    from horaedb_tpu.ops import merge_dedup

    t0 = time.perf_counter()
    waited_for = sorted(map(str, merge_dedup._compiling))
    while merge_dedup._compiling and time.perf_counter() - t0 < timeout_s:
        time.sleep(0.5)
    checks.require(not merge_dedup._compiling,
                   f"background compiles still running: {merge_dedup._compiling}")
    checks.require(not merge_dedup._failed_at,
                   f"background compiles failed: {merge_dedup._failed_at}")
    emit(phase="background_compiles", waited_for=waited_for,
         seconds=round(time.perf_counter() - t0, 3),
         ready=sorted(map(str, merge_dedup._ready)))


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=123)
    p.add_argument("--scale", type=int, default=4000, help="TSBS hosts")
    p.add_argument("--mesh", action="store_true",
                   help="four chips: S1 and S4 over the sharded cache, nothing else")
    p.add_argument("--allow-cpu", action="store_true",
                   help="rehearsal only: run on whatever backend JAX finds")
    args = p.parse_args()
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    t_start = time.perf_counter()
    phases: dict[str, float] = {}
    checks = Checks()

    # 1. device
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    emit(phase="device", jax=jax.__version__, **device)
    if device["platform"] != "tpu" and not args.allow_cpu:
        emit(error=f"JAX found no TPU (platform {device['platform']!r}); "
             "this smoke does not continue on another backend")
        return 1
    if args.mesh and device["count"] != 4:
        emit(error=f"--mesh needs four devices, JAX reports {device['count']}")
        return 1

    # 2. compile cache
    from horaedb_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    emit(phase="compile_cache", dir=cache_dir,
         entries_at_start=len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0)

    import horaedb_tpu
    from horaedb_tpu.tools import tsbs
    from horaedb_tpu.utils import native

    data_dir = tempfile.mkdtemp(prefix="chip_smoke_")
    conn = server = None
    try:
        # 3. load
        t0 = time.perf_counter()
        had_lib = os.path.exists(native._LIB_PATH)
        conn = horaedb_tpu.connect(data_dir)
        conn.execute(
            "CREATE TABLE cpu (hostname string TAG, region string TAG, "
            "datacenter string TAG, "
            + ", ".join(f"{f} double" for f in tsbs.CPU_FIELDS)
            + ", ts timestamp NOT NULL, TIMESTAMP KEY(ts)) "
            "ENGINE=Analytic WITH (segment_duration='2h')"
        )
        rows = tsbs.generate_cpu(args.scale, SPAN_MS, seed=args.seed)
        table = conn.catalog.open("cpu")
        table.write(rows)
        table.flush()
        n_rows = len(rows)
        del rows
        lib = native.load()
        phases["load"] = round(time.perf_counter() - t0, 3)
        emit(phase="load", rows=n_rows, hosts=args.scale, seed=args.seed,
             seconds=phases["load"], data_dir=data_dir,
             native_hashing=("pure-python fallback" if lib is None
                             else "found" if had_lib else "built"))
        checks.require(n_rows == args.scale * (SPAN_MS // tsbs.INTERVAL_MS),
                       f"loaded {n_rows} rows")

        # 4. serve
        t0 = time.perf_counter()
        server = Server(conn)
        deadline = time.monotonic() + 120
        while not server.request("GET", "/health?ready=1").get("ready"):
            if time.monotonic() > deadline:
                raise RuntimeError("server never became ready")
            time.sleep(0.2)
        ex = conn.interpreters.executor
        s1 = tsbs.single_groupby(5, 8, 1)
        if args.mesh:
            statements = [(s1.name, s1.sql, "device-dist"),
                          ("raw-topk-host_7", S4_SQL, "raw_device")]
        else:
            s2, s3 = tsbs.double_groupby_all(1), tsbs.high_cpu_all(1)
            statements = [(s1.name, s1.sql, "device-cached"),
                          (s2.name, s2.sql, "device-cached"),
                          (s3.name, s3.sql, "device-cached"),
                          ("raw-topk-host_7", S4_SQL, "raw_device")]
        for name, sql, device_path in statements:
            reps = run_statement(server, ex, checks, name, sql, device_path)
            if args.mesh:
                checks.require(
                    any(r["path"] == device_path and r.get("mesh_devices") == 4
                        for r in reps),
                    f"{name}: no repeat served over mesh_devices == 4",
                )
        if not args.mesh:
            write_phase(server, ex, checks, args.seed)
        compiled = compile_seconds(server)
        if args.mesh:
            kinds = set(compiled["by_kernel"])
            checks.require(
                {"cached_dist", "raw_topk_dist"} <= kinds,
                f"sharded kernels never dispatched (saw {sorted(kinds)})",
            )
            mesh_residency(ex, checks, devices)
        phases["serve"] = round(time.perf_counter() - t0, 3)

        # 6. residency
        residency(server, conn, checks, devices)
        drain_background_compiles(checks)
        emit(phase="times", wall_seconds=phases, compile=compiled,
             total_seconds=round(time.perf_counter() - t_start, 3))
    finally:
        if server is not None:
            server.close()
        if conn is not None:
            conn.close()
        shutil.rmtree(data_dir, ignore_errors=True)

    if checks.failed:
        emit(smoke="FAILED", failed=checks.failed)
        return 1
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
