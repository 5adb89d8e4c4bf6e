"""horaectl — admin CLI over the server's HTTP API
(ref: the horaectl Rust CLI: cluster list/diagnose/query ops against the
admin HTTP surface, horaectl/src/).

    python -m horaedb_tpu.tools.ctl [--endpoint HOST:PORT] COMMAND

Commands:
    tables                  per-table storage metrics
    query SQL               run a statement, print rows as a table
    route TABLE             show table routing
    block TABLE [...]       add tables to the limiter block-list
    unblock TABLE [...]     remove tables from the block-list
    metrics                 raw Prometheus metrics
    config                  server config dump
    hotspot                 hottest tables by reads/writes
    diagnose                health + config + table summary in one shot
    status                  node status document (/debug/status)
    events tail [--kind K] [--limit N]   engine event journal
    rules list              loaded recording/alert rules (+ rollups)
    rules add NAME EXPR [--kind alert] [--for 30s]   add a runtime rule
    rules rm NAME           remove a runtime rule
    alerts                  alert state (pending/firing/resolved)
    slo                     SLO verdicts: objectives, burn rates, breaches
    device                  device telemetry: HBM residency + compile stats
    livewindow [evict KEY]  live window ring states (state/livewindow)

Shard operations go to the COORDINATOR (``--meta HOST:PORT``):

    split SHARD [--tables a b] [--target NODE]   carve a new shard
    merge SHARD INTO_SHARD                       fold one into another
    migrate SHARD NODE                           move to a named node
    scatter [--max-moves N]                      re-place via hash ring
    procedures                                   coordinator queue state
    elastic [status]                             elastic control-loop state
    elastic release SHARD                        close a shard's circuit breaker
"""

from __future__ import annotations

import argparse
import json
import sys
import urllib.error
import os
import urllib.request

DEFAULT_ENDPOINT = "127.0.0.1:5440"

# Admin auth: --token flag or HORAEDB_TOKEN env (the server's
# server.auth_token gates /admin/* and /debug/*).
_TOKEN = os.environ.get("HORAEDB_TOKEN", "")


def _auth_headers() -> dict:
    return {"Authorization": f"Bearer {_TOKEN}"} if _TOKEN else {}


class CtlError(RuntimeError):
    pass


def _get(endpoint: str, path: str) -> str:
    req = urllib.request.Request(f"http://{endpoint}{path}", headers=_auth_headers())
    try:
        with urllib.request.urlopen(req, timeout=10) as r:
            return r.read().decode()
    except urllib.error.URLError as e:
        raise CtlError(f"GET {path} failed: {e}") from None


def _post(endpoint: str, path: str, payload: dict, method: str = "POST") -> str:
    req = urllib.request.Request(
        f"http://{endpoint}{path}",
        json.dumps(payload).encode(),
        {"Content-Type": "application/json", **_auth_headers()},
        method=method,
    )
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.read().decode()
    except urllib.error.HTTPError as e:
        body = e.read().decode()
        raise CtlError(f"{path} -> {e.code}: {body}") from None
    except urllib.error.URLError as e:
        raise CtlError(f"POST {path} failed: {e}") from None


def _print_rows(rows: list[dict]) -> None:
    if not rows:
        print("(empty)")
        return
    cols = list(rows[0].keys())
    widths = {
        c: max(len(c), *(len(str(r.get(c))) for r in rows)) for c in cols
    }
    print("  ".join(c.ljust(widths[c]) for c in cols))
    print("  ".join("-" * widths[c] for c in cols))
    for r in rows:
        print("  ".join(str(r.get(c)).ljust(widths[c]) for c in cols))


def cmd_tables(ep: str, args) -> None:
    data = json.loads(_get(ep, "/debug/tables"))
    rows = [
        {"table": name, **{k: v for k, v in m.items() if k != "table"}}
        for name, m in sorted(data.items())
    ]
    _print_rows(rows)


def cmd_query(ep: str, args) -> None:
    """``query <sql>`` runs a statement; ``query list`` shows the LIVE
    in-flight registry (system.public.queries); ``query kill <id>``
    cooperatively cancels one (DELETE /debug/queries/{id})."""
    if args.sql == "list" and args.arg is None:
        _print_rows(json.loads(_get(ep, "/debug/queries?live=1")))
        return
    if args.sql == "kill":
        if args.arg is None or not str(args.arg).isdigit():
            raise CtlError("usage: horaectl query kill <query_id>")
        print(_post(ep, f"/debug/queries/{args.arg}", None, method="DELETE"))
        return
    out = json.loads(_post(ep, "/sql", {"query": args.sql}))
    if "rows" in out:
        _print_rows(out["rows"])
    else:
        print(out)


def cmd_route(ep: str, args) -> None:
    print(_get(ep, f"/route/{args.table}"))


def cmd_block(ep: str, args) -> None:
    print(_post(ep, "/admin/block", {"tables": args.tables}))


def cmd_unblock(ep: str, args) -> None:
    print(_post(ep, "/admin/block", {"tables": args.tables}, method="DELETE"))


def cmd_metrics(ep: str, args) -> None:
    print(_get(ep, "/metrics"), end="")


def cmd_config(ep: str, args) -> None:
    print(_get(ep, "/debug/config"))


def cmd_hotspot(ep: str, args) -> None:
    print(_get(ep, "/debug/hotspot"))


def cmd_shards(ep: str, args) -> None:
    print(_get(ep, "/debug/shards"))


def cmd_wal_stats(ep: str, args) -> None:
    print(_get(ep, "/debug/wal_stats"))


def cmd_slow_log(ep: str, args) -> None:
    print(_get(ep, "/debug/slow_log"))


def cmd_compaction(ep: str, args) -> None:
    print(_get(ep, "/debug/compaction"))


def cmd_flush(ep: str, args) -> None:
    path = "/admin/flush" + (f"?table={args.table}" if args.table else "")
    print(_post(ep, path, {}))


def cmd_split(ep: str, args) -> None:
    payload: dict = {"shard_id": args.shard_id}
    if args.tables:
        payload["table_names"] = args.tables
    if args.target:
        payload["target_node"] = args.target
    print(_post(args.meta, "/meta/v1/shard/split", payload))


def cmd_merge(ep: str, args) -> None:
    print(_post(args.meta, "/meta/v1/shard/merge",
                {"shard_id": args.shard_id, "into_shard_id": args.into_shard_id}))


def cmd_migrate(ep: str, args) -> None:
    print(_post(args.meta, "/meta/v1/shard/migrate",
                {"shard_id": args.shard_id, "to_node": args.node}))


def cmd_scatter(ep: str, args) -> None:
    payload = {}
    if args.max_moves is not None:
        payload["max_moves"] = args.max_moves
    print(_post(args.meta, "/meta/v1/shard/scatter", payload))


def cmd_procedures(ep: str, args) -> None:
    print(_get(args.meta, "/meta/v1/procedures"))


def cmd_elastic(ep: str, args) -> None:
    """Elastic control loop (meta/elastic): show the decision-loop state
    or release a quarantined shard's circuit breaker."""
    if args.action == "release":
        if args.shard_id is None:
            raise CtlError("elastic release needs a shard id")
        print(_post(args.meta, "/meta/v1/elastic/release",
                    {"shard_id": args.shard_id}))
        return
    data = json.loads(_get(args.meta, "/meta/v1/elastic"))
    if not data.get("enabled", False):
        print("(elastic control loop not enabled on this coordinator)")
        return
    print(
        f"rounds: {data['rounds']}  holds: {data['holds']}  "
        f"dry_run: {data['dry_run']}"
    )
    print(f"policy: {json.dumps(data['policy'], sort_keys=True)}")
    _print_rows(data.get("shards", []))
    if data.get("quarantined"):
        print(f"\nquarantined: {json.dumps(data['quarantined'], sort_keys=True)}")
    decisions = data.get("recent_decisions", [])
    if decisions:
        print(f"\nrecent decisions ({len(decisions)}):")
        for d in decisions[-10:]:
            print(f"  {json.dumps(d, sort_keys=True)}")


def cmd_status(ep: str, args) -> None:
    """The /debug/status document, flattened one key per line — the
    first thing an operator reads on a node."""
    data = json.loads(_get(ep, "/debug/status"))

    def walk(prefix: str, v) -> None:
        if isinstance(v, dict):
            for k in sorted(v):
                walk(f"{prefix}.{k}" if prefix else k, v[k])
        else:
            print(f"{prefix}: {v}")

    walk("", data)


def cmd_events(ep: str, args) -> None:
    """Tail the engine event journal (/debug/events)."""
    qs = f"?limit={args.limit}"
    if args.kind:
        qs += f"&kind={args.kind}"
    data = json.loads(_get(ep, f"/debug/events{qs}"))
    rows = [
        {
            "seq": e["seq"],
            "timestamp": e["timestamp"],
            "kind": e["kind"],
            "table": e["table"],
            "trace_id": e["trace_id"] if e["trace_id"] is not None else "",
            "attrs": json.dumps(e["attrs"], sort_keys=True),
        }
        for e in data["events"]
    ]
    _print_rows(rows)


def cmd_decisions(ep: str, args) -> None:
    """The decision plane (/debug/decisions): journaled adaptive-loop
    decisions (`decisions list`) or the per-loop calibration verdicts
    and accounting ledger (`decisions calibration`)."""
    if args.action == "calibration":
        data = json.loads(_get(ep, "/debug/decisions?limit=0"))
        rows = [
            {
                "loop": r["loop"],
                "samples": r["samples"],
                "ewma_signed": (
                    round(r["ewma_signed"], 4)
                    if r["ewma_signed"] is not None else ""
                ),
                "ewma_abs": (
                    round(r["ewma_abs"], 4)
                    if r["ewma_abs"] is not None else ""
                ),
                "fast_abs": (
                    round(r["fast_abs"], 4)
                    if r["fast_abs"] is not None else ""
                ),
                "slow_abs": (
                    round(r["slow_abs"], 4)
                    if r["slow_abs"] is not None else ""
                ),
                "miscalibrated": r["miscalibrated"],
                "issued": r["issued"],
                "resolved": r["resolved"],
                "expired": r["expired"],
                "missed": r["missed"],
                "unresolved": r["unresolved"],
            }
            for r in data["calibration"]
        ]
        _print_rows(rows)
        s = data["stats"]
        print(
            f"\nring: size={s['size']}/{s['capacity']}  "
            f"dropped={s['dropped']}  issued={s['issued']}"
        )
        return
    qs = f"?limit={args.limit}"
    if args.loop:
        qs += f"&loop={args.loop}"
    data = json.loads(_get(ep, f"/debug/decisions{qs}"))
    rows = [
        {
            "id": e["id"],
            "timestamp": e["timestamp"],
            "loop": e["loop"],
            "key": e["key"][:48],
            "choice": e["choice"],
            "predicted": (
                round(e["predicted"], 6) if e["predicted"] is not None else ""
            ),
            "actual": (
                round(e["actual"], 6) if e["actual"] is not None else ""
            ),
            "error": (
                round(e["error"], 4) if e["error"] is not None else ""
            ),
            "outcome": e["outcome"],
        }
        for e in data["decisions"]
    ]
    _print_rows(rows)


def cmd_profile(ep: str, args) -> None:
    """The profile plane (/debug/profile): fleetwide wall-clock
    attribution rows aggregated from the server's own span trees,
    sorted by exclusive time."""
    qs = f"?limit={args.limit}"
    if args.path:
        qs += f"&path={args.path}"
    if args.route:
        qs += f"&route={args.route}"
    data = json.loads(_get(ep, f"/debug/profile{qs}"))
    rows = [
        {
            "path": r["path"][:64],
            "route": r["route"],
            "shape": r["shape"][:32],
            "count": r["count"],
            "excl_ms": round(r["exclusive_ms"], 2),
            "total_ms": round(r["total_ms"], 2),
            "ewma_ms": (
                round(r["ewma_ms"], 3) if r["ewma_ms"] is not None else ""
            ),
            "fast_ms": round(r["fast_ms"], 3),
            "slow_ms": round(r["slow_ms"], 3),
            "last_trace": r["last_trace_id"],
        }
        for r in data["profile"]
    ]
    _print_rows(rows)
    s = data["stats"]
    ratio = s["untracked_ratio"]
    print(
        f"\nkeys: {s['keys']}/{s['capacity']}  traces={s['traces']}  "
        f"spans={s['spans']}  dropped={s['dropped']}  "
        f"untracked_ratio={'' if ratio is None else round(ratio, 3)}"
    )


def cmd_rules(ep: str, args) -> None:
    """rules list|add|rm against /admin/rules (mirrors `events tail`)."""
    if args.action == "list":
        data = json.loads(_get(ep, "/admin/rules"))
        rows = [
            {
                "name": r["name"],
                "kind": r["kind"],
                "for_s": r["for_s"],
                "source": r["source"],
                "expr": r["expr"],
                "last_error": r.get("last_error", ""),
            }
            for r in data["rules"]
        ]
        _print_rows(rows)
        if data.get("rollup_tables"):
            print(f"rollup_tables: {', '.join(data['rollup_tables'])}")
        return
    if args.action == "add":
        payload = {
            "name": args.name,
            "expr": " ".join(args.expr),
            "kind": args.kind,
        }
        if getattr(args, "for_", None):
            payload["for"] = args.for_
        print(_post(ep, "/admin/rules", payload))
        return
    # rm
    print(_post(ep, "/admin/rules", {"name": args.name}, method="DELETE"))


def cmd_alerts(ep: str, args) -> None:
    """Current alert state (/debug/alerts)."""
    data = json.loads(_get(ep, "/debug/alerts"))
    if not data.get("enabled", False):
        print("(rules engine disabled on this node)")
        return
    rows = [
        {
            "rule": a["rule"],
            "state": a["state"],
            "value": a["value"],
            "labels": json.dumps(a["labels"], sort_keys=True),
            "active_since_ms": a["active_since_ms"],
            "fired_at_ms": a["fired_at_ms"],
        }
        for a in data["alerts"]
    ]
    _print_rows(rows)


def cmd_slo(ep: str, args) -> None:
    """SLO verdicts (/debug/slo): one line per objective — state, the
    current indicator value vs bound, fast/slow burn rates — then the
    breach history (ok -> burning transitions, newest last)."""
    data = json.loads(_get(ep, "/debug/slo"))
    if not data.get("enabled", False):
        print("(no SLO objectives on this node)")
        return
    rows = [
        {
            "objective": o["name"],
            "state": o["state"],
            "value": "" if o["value"] is None else round(o["value"], 6),
            "bound": o["bound"],
            "target": f"{o['target'] * 100:g}%",
            "burn_fast": o["burn_fast"],
            "burn_slow": o["burn_slow"],
            "breaches": o["breaches"],
            "last_error": o.get("last_error", ""),
        }
        for o in data["objectives"]
    ]
    _print_rows(rows)
    breaches = data.get("breaches", [])
    if breaches:
        print(f"\nbreach history ({len(breaches)}):")
        _print_rows(
            [
                {
                    "objective": b["objective"],
                    "at_ms": b["at_ms"],
                    "value": b["value"],
                    "burn_fast": b["burn_fast"],
                    "burn_slow": b["burn_slow"],
                    "recovered_at_ms": b["recovered_at_ms"] or "(burning)",
                }
                for b in breaches
            ]
        )


def cmd_device(ep: str, args) -> None:
    """The device telemetry plane (/debug/device): per-(table, column)
    HBM residency inventory, byte totals by component, and per-kernel
    compile-cache stats — the CLI face of ``system.public.device``."""
    data = json.loads(_get(ep, "/debug/device"))
    if not data.get("enabled", True):
        print("(device telemetry disabled: HORAEDB_DEVICE_TELEMETRY=0)")
        return
    rows = [
        {
            "table": r["table_name"],
            "column": r["column_name"],
            "component": r["component"],
            "dtype": r["dtype"],
            "bytes": r["bytes"],
            "rows": r["rows"],
            "last_hit_age_ms": r["last_hit_age_ms"],
            "evictions": r["evictions"],
        }
        for r in data.get("inventory", [])
    ]
    _print_rows(rows)
    totals = data.get("totals", {})
    print(
        "\ntotals: "
        + "  ".join(f"{k}={v}" for k, v in sorted(totals.items()))
    )
    compile_stats = data.get("compile", {})
    if compile_stats:
        print("\ncompile cache (per kernel kind):")
        _print_rows(
            [
                {"kernel": k, "compiles": v["compiles"], "hits": v["hits"]}
                for k, v in sorted(compile_stats.items())
            ]
        )


def cmd_livewindow(ep: str, args) -> None:
    """Live window state plane (/debug/livewindow): resident device ring
    states, shapes pending promotion, and the byte budget — `livewindow
    evict KEY` drops one state (journaled as an eviction)."""
    if args.action == "evict":
        if not args.key:
            raise CtlError("livewindow evict needs a state KEY")
        print(_post(ep, f"/debug/livewindow/{args.key}", {}, method="DELETE").strip())
        return
    data = json.loads(_get(ep, "/debug/livewindow"))
    if not data.get("enabled", True):
        print("(live window state disabled: HORAEDB_LIVEWINDOW=0)")
        return
    _print_rows(
        [
            {
                "key": s["key"],
                "table": s["table"],
                "window_ms": s["window_ms"],
                "depth": s["depth"],
                "groups": s["groups"],
                "bytes": s["bytes"],
                "head_bucket": s["head_bucket"],
                "dirty": s["dirty_buckets"],
                "counter_dirty": s["counter_dirty"],
                "reads_served": s["reads_served"],
            }
            for s in data.get("states", [])
        ]
    )
    print(
        f"\nresident {data.get('resident_bytes', 0)} / "
        f"budget {data.get('budget_bytes', 0)} bytes"
    )
    pending = data.get("pending", {})
    if pending:
        print("pending promotion (shape: eligible reads seen):")
        for k, n in sorted(pending.items()):
            print(f"  {k}: {n}")


def cmd_diagnose(ep: str, args) -> None:
    print("health:  ", _get(ep, "/health").strip())
    print("config:  ", _get(ep, "/debug/config").strip())
    data = json.loads(_get(ep, "/debug/tables"))
    print(f"tables:   {len(data)}")
    for name, m in sorted(data.items()):
        print(f"  {name}: {m}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="horaectl", description=__doc__)
    p.add_argument("--endpoint", default=DEFAULT_ENDPOINT)
    p.add_argument("--token", default=None, help="admin auth token (or HORAEDB_TOKEN env)")
    sub = p.add_subparsers(dest="command", required=True)
    sub.add_parser("tables")
    q = sub.add_parser("query")
    q.add_argument("sql", help="SQL text, or the verbs 'list' / 'kill'")
    q.add_argument("arg", nargs="?", default=None,
                   help="query id for 'kill'")
    r = sub.add_parser("route")
    r.add_argument("table")
    b = sub.add_parser("block")
    b.add_argument("tables", nargs="+")
    u = sub.add_parser("unblock")
    u.add_argument("tables", nargs="+")
    sub.add_parser("metrics")
    sub.add_parser("config")
    sub.add_parser("hotspot")
    sub.add_parser("diagnose")
    sub.add_parser("status")
    ev = sub.add_parser("events")
    ev.add_argument("action", nargs="?", default="tail", choices=["tail"])
    ev.add_argument("--kind", default=None)
    ev.add_argument("--limit", type=int, default=20)
    de = sub.add_parser("decisions")
    de.add_argument("action", nargs="?", default="list",
                    choices=["list", "calibration"])
    de.add_argument("--loop", default=None)
    de.add_argument("--limit", type=int, default=20)
    pf = sub.add_parser("profile")
    pf.add_argument("--path", default=None)
    pf.add_argument("--route", default=None)
    pf.add_argument("--limit", type=int, default=20)
    rl = sub.add_parser("rules")
    rl_sub = rl.add_subparsers(dest="action", required=True)
    rl_sub.add_parser("list")
    rl_add = rl_sub.add_parser("add")
    rl_add.add_argument("name")
    rl_add.add_argument("expr", nargs="+", help="PromQL expression")
    rl_add.add_argument("--kind", default="recording",
                        choices=["recording", "alert"])
    rl_add.add_argument("--for", dest="for_", default=None,
                        help="alert for-duration, e.g. 30s")
    rl_rm = rl_sub.add_parser("rm")
    rl_rm.add_argument("name")
    sub.add_parser("alerts")
    sub.add_parser("slo")
    sub.add_parser("device")
    lw = sub.add_parser("livewindow")
    lw.add_argument("action", nargs="?", default="list",
                    choices=["list", "evict"])
    lw.add_argument("key", nargs="?", default=None)
    sub.add_parser("shards")
    sub.add_parser("wal_stats")
    sub.add_parser("slow_log")
    sub.add_parser("compaction")
    fl = sub.add_parser("flush")
    fl.add_argument("table", nargs="?", default=None)
    meta_default = os.environ.get("HORAEDB_META", "127.0.0.1:2379")
    sp = sub.add_parser("split")
    sp.add_argument("shard_id", type=int)
    sp.add_argument("--tables", nargs="*", default=None)
    sp.add_argument("--target", default=None)
    sp.add_argument("--meta", default=meta_default)
    mg = sub.add_parser("merge")
    mg.add_argument("shard_id", type=int)
    mg.add_argument("into_shard_id", type=int)
    mg.add_argument("--meta", default=meta_default)
    mi = sub.add_parser("migrate")
    mi.add_argument("shard_id", type=int)
    mi.add_argument("node")
    mi.add_argument("--meta", default=meta_default)
    sc = sub.add_parser("scatter")
    sc.add_argument("--max-moves", type=int, default=None)
    sc.add_argument("--meta", default=meta_default)
    pr = sub.add_parser("procedures")
    pr.add_argument("--meta", default=meta_default)
    el = sub.add_parser("elastic")
    el.add_argument("action", nargs="?", default="status",
                    choices=["status", "release"])
    el.add_argument("shard_id", nargs="?", type=int, default=None)
    el.add_argument("--meta", default=meta_default)
    args = p.parse_args(argv)
    if args.token:
        global _TOKEN
        _TOKEN = args.token
    handler = globals()[f"cmd_{args.command}"]
    try:
        handler(args.endpoint, args)
    except CtlError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    except BrokenPipeError:
        # Piped into head/less and the reader closed first — unix says
        # exit quietly, not with a traceback.
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        return 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
