"""Adaptive device/host path routing for aggregate queries.

The reference picks execution resources per query with a static rule
(expensive-query classification by time range -> priority runtime,
query_frontend/src/plan.rs:105, components/runtime/src/priority_runtime.rs);
this is the TPU-native generalization: the profitable path depends on the
accelerator's dispatch latency, which varies by deployment (an attached
chip ~us; a remote one ~ms). Instead of a static threshold,
the router MEASURES both paths per query shape and serves from the winner,
re-probing the loser so it adapts when conditions change (scan cache
finishes building, data grows, dispatch latency shifts).

How a router keeps its estimate of a losing route (``_ProbeSchedule``,
one rule for ``PathRouter`` and ``kernel_choice.KernelRouter``):

* Only a clean sample becomes an estimate. A request during which a
  program compiled or the scan cache was built says nothing of a route's
  speed: the executor reports it as not ``clean`` and it is dropped (up
  to ``PROBE_EVERY`` in a row; a shape that compiles on every call really
  is that slow, so the next one is folded whatever it carried).
* A loser is re-probed by a share of serving time, not by a count of
  calls: once the winner has served for ``PROBE_EVERY`` times the loser's
  estimate since the loser was last sampled, each serve counted at the
  winner's estimate. Re-measuring a loser then costs at most one part in
  ``PROBE_EVERY + 1`` of the serving time whatever it lost by (at equal
  times that is every 17th call, and never more often however the
  samples spread; a loser 60x slower is probed every ~960 serves).
* One sample is not trusted for long: a loser whose estimate rests on a
  single sample is confirmed after ``PROBE_EVERY`` calls of the winner,
  and moves to the budget above from its second sample on.
* A winner that slows past the loser's estimate flips the route at once,
  with no probe needed.

Keyed by (table, select-statement shape): repeated dashboard/TSBS-style
queries converge after one probe of each path. Latencies fold into an EWMA
so a single GC hiccup or latency blip doesn't flip the decision.

Enabled when the JAX backend is not ``cpu`` (override with
HORAEDB_ADAPTIVE_PATH=0/1): on the host backend "device" dispatch is
in-process and the device path's own thresholds already apply.
"""

from __future__ import annotations

import dataclasses
import os
import threading

from ..utils.metrics import REGISTRY

# serve the winner; a loser's re-probes get one part in PROBE_EVERY of the
# time the winner serves
PROBE_EVERY = 16
MAX_KEYS = 512  # LRU bound on tracked query shapes

# Eager registration: both routers' counters exist at 0 from the first
# scrape, so a window without probes reads 0 and not "no such counter".
_PROBES = {
    router: REGISTRY.counter(
        "horaedb_router_probes_total",
        "requests a router sent down a losing route to re-measure it",
        labels={"router": router},
    )
    for router in ("path", "kernel")
}


class _ProbeSchedule:
    """The estimates and the probe schedule both routers share.

    Per key, ``st["t"]`` maps a route to its estimated time, ``st["n"]``
    to the samples folded into it, and ``st["since"]`` to what the winner
    has served since the route was last sampled or last handed out as a
    probe — counted in the route's own estimates, so it is due at
    ``PROBE_EVERY`` (a route with one sample: counted in calls). The rule
    has no unit and reads no clock: samples may be seconds (the query
    routers) or seconds per row (engine/merge.py), and only their ratios
    matter."""

    router = ""  # the ``horaedb_router_probes_total`` label

    def __init__(self) -> None:
        self._stats: dict = {}
        self._lock = threading.Lock()

    def _touch(self, key) -> dict:
        """stats entry for key, LRU-bumped; evicts the oldest past MAX_KEYS
        (dicts preserve insertion order — re-inserting moves to the back)."""
        st = self._stats.pop(key, None)
        if st is None:
            st = {"t": {}, "n": {}, "since": {}}
            if len(self._stats) >= MAX_KEYS:
                self._stats.pop(next(iter(self._stats)))
        self._stats[key] = st
        return st

    @staticmethod
    def _fold(st: dict, route: str, seconds: float) -> None:
        """Fold one clean sample in. The estimate adapts DOWN instantly (a
        faster time is proof the route can go that fast) and creeps UP by
        10% per sample (one GC pause or dispatch hiccup must not flip the
        route). A sample of the route that now wins is serving time, and
        pays into every other route's probe budget — at the estimate just
        folded, which is never more than the sample: one slow sample (a GC
        pause, a wait for the GIL) cannot buy a probe, so no loser is
        probed more often than one call in ``PROBE_EVERY + 1`` however the
        samples spread. A loser with a single sample is paid a whole call:
        whatever tainted that sample, it is confirmed after ``PROBE_EVERY``
        calls, not after ``PROBE_EVERY`` times itself."""
        times, n, since = st["t"], st["n"], st["since"]
        prev = times.get(route)
        est = times[route] = seconds if prev is None else min(seconds, prev * 1.1)
        n[route] = n.get(route, 0) + 1
        since[route] = 0.0
        if all(est <= t for t in times.values()):
            for k, t in times.items():
                if k != route:
                    since[k] += est / t if n[k] > 1 and t > 0 else 1.0

    def _due_probe(self, st: dict, losers):
        """The loser to re-measure on this call, or None: one whose budget
        is full, the most overdue first. The budget is spent at hand-out,
        so concurrent callers do not all take the probe while the first is
        still in flight."""
        since = st["since"]
        due = [k for k in losers if since[k] >= PROBE_EVERY]
        if not due:
            return None
        probe = max(due, key=since.get)
        since[probe] = 0.0
        _PROBES[self.router].inc()
        return probe


def plan_shape_key(plan) -> tuple:
    """(table, normalized-select) with literal VALUES masked out.

    Rolling-window dashboards re-issue the same query with fresh time/
    filter literals every refresh; masking literals makes those one shape,
    so the router's samples accumulate instead of restarting (and the
    stats table stays bounded)."""
    return (plan.table, _shape(plan.select))


def _shape(node):
    if dataclasses.is_dataclass(node) and not isinstance(node, type):
        if type(node).__name__ == "Literal":
            return ("?",)  # value masked; shape only
        return (
            type(node).__name__,
            *(
                (f.name, _shape(getattr(node, f.name)))
                for f in dataclasses.fields(node)
            ),
        )
    if isinstance(node, (tuple, list)):
        return tuple(_shape(x) for x in node)
    return node


class PathRouter(_ProbeSchedule):
    router = "path"

    def choose(self, key) -> str:
        """"device" or "host".

        "device" until the device holds one CLEAN sample: the first device
        executions of a query shape pay jit trace+compile (the uncached
        program, then the cached one) and the scan cache's deferred build
        (scan_cache builds on the second sighting of a stable base state)
        — none reflects steady-state serving, and ``record`` drops them.
        Then one host sample, then the measured winner, with the loser
        re-probed as its budget allows (``_due_probe``).
        """
        with self._lock:
            st = self._touch(key)
            times = st["t"]
            if "device" not in times:
                return "device"
            if "host" not in times:
                return "host"
            winner, loser = (
                ("device", "host") if times["device"] <= times["host"]
                else ("host", "device")
            )
            return self._due_probe(st, (loser,)) or winner

    def record(self, key, kind: str, seconds: float, clean: bool = True) -> None:
        """Fold a sample in (``_fold``). ``clean=False``: a program was
        compiled or the scan cache built while the request ran — dropped,
        unless ``PROBE_EVERY`` such samples of the route came in a row
        before it."""
        with self._lock:
            st = self._touch(key)
            tainted = st.setdefault("tainted", {})
            dropped = tainted.pop(kind, 0)  # in a row, of this route
            if not clean and dropped < PROBE_EVERY:
                tainted[kind] = dropped + 1
                return
            self._fold(st, kind, seconds)

    def stats(self, key) -> dict:
        """{"device": s, "host": s, "n": {route: samples}, "since": {...}}."""
        with self._lock:
            st = self._stats.get(key)
            if st is None:
                return {}
            return {**st["t"], "n": dict(st["n"]), "since": dict(st["since"])}


def adaptive_enabled() -> bool:
    v = os.environ.get("HORAEDB_ADAPTIVE_PATH", "auto")
    if v in ("0", "off", "false"):
        return False
    if v in ("1", "on", "true"):
        return True
    import jax

    return jax.default_backend() != "cpu"


def raw_adaptive_enabled() -> bool:
    """Adaptive routing for RAW (non-aggregate) reads. Defaults ON for
    every backend — unlike the aggregate kernels (where device wins and
    "auto" only worries about dispatch latency), raw device-vs-host
    genuinely flips with table size/selectivity on XLA-CPU too.
    HORAEDB_ADAPTIVE_PATH=0 still pins routing off (device-first)."""
    v = os.environ.get("HORAEDB_ADAPTIVE_PATH", "auto")
    return v not in ("0", "off", "false")
