"""Adaptive device/host path routing for aggregate queries.

The reference picks execution resources per query with a static rule
(expensive-query classification by time range -> priority runtime,
query_frontend/src/plan.rs:105, components/runtime/src/priority_runtime.rs);
this is the TPU-native generalization: the profitable path depends on the
accelerator's dispatch latency, which varies by deployment (an attached
chip ~us; a remote one ~ms). Instead of a static threshold,
the router MEASURES both paths per query shape and serves from the winner,
re-probing the loser on a fixed cadence so it adapts when conditions change
(scan cache finishes building, data grows, dispatch latency shifts).

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

PROBE_EVERY = 16  # serve the winner; re-probe the loser every Nth call
MAX_KEYS = 512  # LRU bound on tracked query shapes


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


class PathRouter:
    def __init__(self) -> None:
        # key -> {"device": s, "host": s, "device_n": int, "calls": int}
        self._stats: dict = {}
        self._lock = threading.Lock()

    def _touch(self, key) -> dict:
        """stats entry for key, LRU-bumped; evicts the oldest past MAX_KEYS
        (dicts preserve insertion order — re-inserting moves to the back)."""
        st = self._stats.pop(key, None)
        if st is None:
            st = {"calls": 0}
            if len(self._stats) >= MAX_KEYS:
                self._stats.pop(next(iter(self._stats)))
        self._stats[key] = st
        return st

    def choose(self, key) -> str:
        """"device" or "host".

        Collects TWO device samples before judging: the first device
        execution of a query shape pays jit trace+compile, and the second
        typically absorbs the scan cache's deferred build (scan_cache
        builds on the second sighting of a stable base state) — neither
        reflects steady-state serving. Then one host sample, then the
        measured winner with periodic probes of the loser.
        """
        with self._lock:
            st = self._touch(key)
            if st.get("device_n", 0) < 2:
                return "device"
            if "host" not in st:
                return "host"
            st["calls"] += 1
            winner = "device" if st["device"] <= st["host"] else "host"
            if st["calls"] % PROBE_EVERY == 0:
                return "host" if winner == "device" else "device"
            return winner

    def record(self, key, kind: str, seconds: float) -> None:
        """Fold a sample in: adapt DOWN instantly (a faster time is proof
        the path can go that fast), creep UP by 10% per sample (one GC
        pause or dispatch hiccup must not flip the route)."""
        with self._lock:
            st = self._touch(key)
            prev = st.get(kind)
            if kind == "device":
                n = st.get("device_n", 0) + 1
                st["device_n"] = n
                if n == 2:
                    prev = None  # drop the compile-tainted first sample
            st[kind] = seconds if prev is None else min(seconds, prev * 1.1)

    def stats(self, key) -> dict:
        with self._lock:
            return dict(self._stats.get(key, {}))


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


# ---- learned segment-kernel routing ---------------------------------------
#
# The device group-by has three segment-reduction impls (ops/scan_agg.py:
# mxu one-hot matmul, scatter segment_* ops, hash slot table) and the
# winner flips with group cardinality and skew (arXiv 2411.13245) — a
# static import-time threshold leaves a regime on the table on every
# deployment. Same EWMA + periodic-reprobe machinery as PathRouter, one
# level down: keyed by (plan shape, segment-count bucket), choosing the
# IMPL the jitted kernel branches on instead of the device/host path.
# The first call of a shape is seeded from estimated group cardinality
# (sampler/exact group encoding + observed query_stats history), so it
# already starts near the winner instead of probing blind.


def kernel_routing_enabled() -> bool:
    """Learned impl choice (default on — it matters on every backend;
    scatter-vs-hash flips on CPU too). HORAEDB_SEGMENT_IMPL pinning
    bypasses the router entirely regardless of this switch."""
    return os.environ.get("HORAEDB_KERNEL_ROUTER", "1") not in (
        "0", "off", "false",
    )


def candidate_kernels(n_seg: int, n_rows: int, est_distinct=None,
                      n_fields: int = 0, need_minmax: bool = False) -> tuple:
    """Impls worth PROBING for this shape. Routing must never schedule a
    probe that is catastrophically wrong by construction: the MXU one-hot
    is O(N * n_seg) — beyond a bounded extrapolation of the static
    crossover a single probe could cost seconds — and the hash table
    cannot beat the direct impls when the domain is already tiny or the
    live cardinality fills most of it (a near-full table just routes
    everything through the overflow fallback). Nor one the device would
    refuse: an impl whose temporaries (``segment_temp_bytes``) exceed the
    device's free memory is not offered — () when none fits, and the host
    serves the query."""
    import jax

    from ..obs.device import device_free_bytes
    from ..ops.scan_agg import mxu_max_segments, segment_temp_bytes

    cands = ["scatter"]
    if n_seg <= (
        # the 4x extrapolation is MXU-calibrated; without a matrix unit
        # the one-hot's O(N * n_seg) bites orders of magnitude sooner
        4 * mxu_max_segments() if jax.default_backend() == "tpu" else 256
    ):
        cands.append("mxu")
    if n_seg > 64 and (est_distinct is None or est_distinct * 4 <= n_seg):
        cands.append("hash")
    free = device_free_bytes()
    if free is not None:
        cands = [
            k for k in cands
            if segment_temp_bytes(k, n_rows, n_seg, n_fields, need_minmax) <= free
        ]
    return tuple(cands)


def seed_kernel(n_seg: int, est_distinct, backend: str) -> str:
    """Cardinality-seeded starting impl for a never-measured shape."""
    if (
        est_distinct is not None
        and n_seg > 512
        and est_distinct * 8 <= n_seg
    ):
        # Sparse domain: most segments provably empty — hash territory.
        return "hash"
    from ..ops.scan_agg import mxu_max_segments

    if backend == "tpu":
        return "mxu" if n_seg <= mxu_max_segments() else "scatter"
    return "scatter"


class KernelRouter:
    """Per-(plan-shape, segment-bucket) EWMA over the segment impls.

    Same discipline as PathRouter: warm each candidate (dropping its
    compile-tainted first sample), serve the measured winner, re-probe
    the losers round-robin every PROBE_EVERY-th call so the choice
    adapts when conditions change. Also remembers the observed live
    segment count per key — the feedback that sizes the hash slot table
    and corrects a bad seed estimate."""

    def __init__(self) -> None:
        self._stats: dict = {}
        self._lock = threading.Lock()

    def _touch(self, key) -> dict:
        st = self._stats.pop(key, None)
        if st is None:
            st = {"calls": 0, "n": {}, "t": {}}
            if len(self._stats) >= MAX_KEYS:
                self._stats.pop(next(iter(self._stats)))
        self._stats[key] = st
        return st

    def choose(self, key, seed: str, candidates: tuple):
        """The impl to dispatch this call with; None when there is none to
        offer: ``candidates`` is empty, or the device has refused every one
        of them for this key (``refuse``)."""
        with self._lock:
            st = self._touch(key)
            st["calls"] += 1
            refused = st.get("refused", ())
            candidates = tuple(k for k in candidates if k not in refused)
            if not candidates:
                return None
            samples, times = st["n"], st["t"]
            order = [seed] + [k for k in candidates if k != seed]
            for k in order:
                # two samples each: the first pays jit trace+compile and
                # is dropped by record() — judging needs a clean one
                if k in candidates and samples.get(k, 0) < 2:
                    return k
            measured = {k: times[k] for k in candidates if k in times}
            if not measured:
                return seed if seed in candidates else candidates[0]
            winner = min(measured, key=measured.get)
            if st["calls"] % PROBE_EVERY == 0:
                losers = [k for k in candidates if k != winner]
                if losers:
                    return losers[(st["calls"] // PROBE_EVERY) % len(losers)]
            return winner

    def record(self, key, kernel: str, seconds: float) -> None:
        """Fold a dispatch latency in: adapt DOWN instantly, creep UP by
        10% per sample; the first sample of each impl (compile-tainted)
        only counts, never judges."""
        with self._lock:
            st = self._touch(key)
            n = st["n"][kernel] = st["n"].get(kernel, 0) + 1
            if n == 1:
                return  # compile-tainted
            prev = st["t"].get(kernel)
            st["t"][kernel] = (
                seconds if prev is None else min(seconds, prev * 1.1)
            )

    def refuse(self, key, kernel: str) -> None:
        """The device refused ``kernel``'s program for this key (no room in
        HBM): never offer it for the key again."""
        with self._lock:
            self._touch(key).setdefault("refused", set()).add(kernel)

    def note_segments(self, key, live: int) -> None:
        """Observed live (group x bucket) cells — EWMA'd so the hash
        slot table is sized from what the shape actually produces."""
        with self._lock:
            st = self._touch(key)
            prev = st.get("segments")
            st["segments"] = (
                int(live) if prev is None else int(0.7 * prev + 0.3 * live)
            )

    def observed_segments(self, key):
        with self._lock:
            st = self._stats.get(key)
            return None if st is None else st.get("segments")

    def stats(self, key) -> dict:
        with self._lock:
            st = self._stats.get(key, {})
            return {
                k: (type(v)(v) if isinstance(v, (dict, set)) else v)
                for k, v in st.items()
            }

    def reset(self) -> None:
        with self._lock:
            self._stats.clear()


# One process-wide router: kernel latency is a property of the hardware
# and the shape, not of any particular executor instance — every
# consumer (direct device path, cached path, dist-agg step) folds into
# and serves from the same history.
KERNEL_ROUTER = KernelRouter()


def bootstrap_observed_segments(sql: str):
    """Seed a never-seen router key from query_stats history: the most
    recent finalized ledger of the same normalized SQL shape carries the
    live segment count its aggregation produced (``agg_segments``)."""
    if not sql:
        return None
    from ..utils.querystats import STATS_STORE
    from ..wlm.admission import normalize_shape

    shape = normalize_shape(sql)
    for row in reversed(STATS_STORE.list()):
        segs = row.get("agg_segments")
        if segs and normalize_shape(str(row.get("sql", ""))) == shape:
            return int(segs)
    return None
