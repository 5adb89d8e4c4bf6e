"""Device telemetry plane — HBM occupancy, kernel timing, and compile
accounting as first-class observables.

The host-side observability stack (docs/OBSERVABILITY.md) answers *when*
(span trees) and *what it cost* (the query ledger), but the device plane
was dark: nothing reported what HBM is spent on, how long dispatches
actually run on-device, or when/why XLA recompiles. StreamBox-HBM
(PAPERS.md) treats HBM residency as a first-class managed resource, and
"Fine-Tuning Data Structures for Analytical Query Processing" argues
layout/route decisions are only tunable when their cost counters are
first-class — the compressed-storage auto-tuner and incremental-window
eviction (ROADMAP items 2 and 4) read the usage map this module serves.

Four legs:

1. **HBM occupancy** — a per-(table, column, dtype) residency inventory
   derived from the scan cache's own ``device_bytes`` accounting (plus
   session/stack uploads, and any future partial-agg/window state via
   ``register_occupancy_provider``), served as ``system.public.device``
   and ``/debug/device`` with bytes, rows, dtype, last-hit age, and
   eviction counts.
2. **Kernel timing** — ``timed_dispatch(kind, fn)`` wraps every device
   dispatch point (cached agg packed/dist/cohort, raw top-k/selection,
   the fused direct/partial kernel). EVERY dispatch is timed: each
   dispatch point fetches its result in the next statement, so one
   ``block_until_ready`` serialises nothing that was not serial. The
   launch and the wait are the spans ``dispatch`` and ``device_wait``
   (utils/tracectx, so they are on the profiler's clock too); launch ->
   ready lands in the ledger (``device_ms``, ``device_dispatches``)
   and the per-kernel ``horaedb_device_dispatch_seconds`` histograms.
3. **Compile accounting** — ``utils/querystats.note_kernel_dispatch``
   routes first-seen static shapes here: a typed ``kernel_compile``
   event (kind, shape bucket, wall ms, XLA ``cost_analysis``
   flops/bytes where available) lands in the journal, the per-kernel
   compile histogram/counters tick, and the ledger's ``compile_hit``
   marks the query that paid the stall.
4. **Surfaces** — ``/debug/device`` (server/http.py), ``horaectl
   device`` (tools/ctl.py), ``system.public.device``
   (table_engine/system.py); the ``horaedb_device_*`` families ride the
   self-monitoring recorder into ``system_metrics.samples`` like every
   other family.

``HORAEDB_DEVICE_TELEMETRY=0`` turns the whole plane off (dispatch
wrappers become bare calls).
"""

from __future__ import annotations

import time
import weakref
from typing import Any, Callable, Optional

from ..utils.metrics import REGISTRY
from ..utils.tracectx import span

# Every device-dispatch point declares its kernel kind here — the label
# set of the horaedb_device_* families (eagerly registered, lint-pinned
# like SEGMENT_KERNEL_LABELS / RAW_SCAN_PATHS).
DEVICE_KERNEL_KINDS = (
    "cached_packed",   # RTT-minimized packed cached agg (single device)
    "cached_dist",     # shard_map cached agg over the serving mesh
    "cached_cohort",   # vmapped fused cohort dispatch (wlm/batch)
    "fused",           # direct/partial fused scan-agg (ops/scan_agg)
    "fused_dist",      # its shard_map form (parallel/dist_agg)
    "raw_topk",        # raw read: bisection top-k (ops/scan_topk)
    "raw_select",      # raw read: bounded selection
    "raw_topk_dist",   # sharded raw variants (parallel/dist_raw)
    "raw_select_dist",
    "state_fold",      # live-window ring fold/gather (ops/livewindow)
)

# Occupancy row components: "column" rows sum to the scan cache's own
# device_bytes accounting (the acceptance invariant); "session"/"stack"
# are the content-keyed query-shape uploads and stacked value views the
# cache keeps beside the columns; "evicted" rows carry eviction counts
# for tables no longer resident.
OCCUPANCY_COMPONENTS = ("column", "session", "stack", "evicted", "state")

# Registry discipline (lint-enforced like the agg-kernel/raw families):
# declared here, registered eagerly, documented in docs/OBSERVABILITY.md,
# and no stray horaedb_device_* family may exist outside this tuple.
DEVICE_METRIC_FAMILIES = (
    "horaedb_device_dispatch_total",
    "horaedb_device_dispatch_seconds",
    "horaedb_device_compile_total",
    "horaedb_device_compile_seconds",
    "horaedb_device_resident_bytes",
    "horaedb_device_evictions_total",
)

# Device dispatches are sub-ms..s on real chips; the default bucket
# ladder starts at 1ms and would fold the whole fast path into one
# bucket.
_DISPATCH_BUCKETS = (
    0.0001, 0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0,
)

_M_DISPATCH = {
    k: REGISTRY.counter(
        "horaedb_device_dispatch_total",
        "device kernel dispatches, by kernel kind",
        labels={"kernel": k},
    )
    for k in DEVICE_KERNEL_KINDS
}
_M_DISPATCH_SECONDS = {
    k: REGISTRY.histogram(
        "horaedb_device_dispatch_seconds",
        "device dispatch wall seconds, launch -> ready (block_until_ready)",
        buckets=_DISPATCH_BUCKETS,
        labels={"kernel": k},
    )
    for k in DEVICE_KERNEL_KINDS
}
_M_COMPILE_SECONDS = {
    k: REGISTRY.histogram(
        "horaedb_device_compile_seconds",
        "wall seconds of first-time XLA compiles, by kernel kind",
        labels={"kernel": k},
    )
    for k in DEVICE_KERNEL_KINDS
}
_M_COMPILE = {
    (k, outcome): REGISTRY.counter(
        "horaedb_device_compile_total",
        "compile-cache outcomes per device dispatch shape, by kernel kind",
        labels={"kernel": k, "outcome": outcome},
    )
    for k in DEVICE_KERNEL_KINDS
    for outcome in ("compile", "hit")
}
# What the sharded programs' final aggregate moves between the chips: set by
# the static shapes (parallel/dist_agg.combine_bytes), counted per dispatch.
_M_COMBINE_BYTES = REGISTRY.counter(
    "horaedb_dist_combine_bytes_total",
    "bytes each device handed the mesh collectives that combine the "
    "per-device partial aggregates of sharded dispatches",
)
# How many scatter steps reduced each run of equal segment ids to one update
# row (ops/scan_agg._fits), summed over a mesh's chips on the device.
_M_FOLDED_CHUNKS = REGISTRY.counter(
    "horaedb_scan_folded_chunks_total",
    "row chunks whose segment scatter ran one update row per run of equal "
    "segment ids, summed over chips",
)
# Dispatches whose program read the per-series tables through the series
# codes' 128-row blocks (ops/encoding.reads_by_block), not one row at a time.
_M_BLOCK_LOOKUPS = REGISTRY.counter(
    "horaedb_scan_block_lookups_total",
    "full-scan dispatches that looked the per-series tables up through each "
    "128-row block's few candidate series instead of per row",
)
_M_RESIDENT = {
    c: REGISTRY.gauge(
        "horaedb_device_resident_bytes",
        "HBM-resident bytes by component (scan-cache columns/sessions/stacks)",
        labels={"component": c},
    )
    for c in ("column", "session", "stack")
}
_M_EVICTIONS = REGISTRY.counter(
    "horaedb_device_evictions_total",
    "scan-cache entries evicted under the HBM byte/entry budget",
)


# ---- knobs -----------------------------------------------------------------


def device_telemetry_enabled() -> bool:
    """HORAEDB_DEVICE_TELEMETRY=0 turns the plane off entirely (the
    dispatch wrappers become bare calls — the bench A/B's off arm)."""
    import os

    return os.environ.get("HORAEDB_DEVICE_TELEMETRY", "1") != "0"


# ---- kernel timing ---------------------------------------------------------


def timed_dispatch(kind: str, fn: Callable[[], Any], **attrs: Any) -> Any:
    """Run one device dispatch and wait for its result; returns ``fn()``'s
    result unchanged.

    Two spans: ``dispatch`` around ``fn()`` alone (host time to launch —
    the call returns before the device is done; ``attrs`` such as
    ``impl``/``program`` land on it beside ``kernel``) and
    ``device_wait`` around one ``block_until_ready``. Bumps
    ``horaedb_device_dispatch_total{kernel=}`` and the ledger's
    ``device_dispatches``, observes the per-kernel dispatch histogram
    and adds launch -> ready milliseconds to the ledger's ``device_ms``.
    Telemetry off: a bare call."""
    if not device_telemetry_enabled():
        return fn()
    import jax

    from ..utils import querystats

    t0 = time.perf_counter()
    with span("dispatch", kernel=kind, **attrs):
        out = fn()
    with span("device_wait"):
        try:
            jax.block_until_ready(out)
        except Exception:
            pass  # a failed program raises where its result is fetched
    dt = time.perf_counter() - t0
    counter = _M_DISPATCH.get(kind)
    if counter is None:  # undeclared kind: account it, lint will flag
        counter = REGISTRY.counter(
            "horaedb_device_dispatch_total",
            "device kernel dispatches, by kernel kind",
            labels={"kernel": kind},
        )
    counter.inc()
    hist = _M_DISPATCH_SECONDS.get(kind)
    if hist is None:
        hist = REGISTRY.histogram(
            "horaedb_device_dispatch_seconds",
            "device dispatch wall seconds, launch -> ready (block_until_ready)",
            buckets=_DISPATCH_BUCKETS,
            labels={"kernel": kind},
        )
    hist.observe(dt)
    querystats.record(device_dispatches=1, device_ms=dt * 1000.0)
    return out


# ---- compile accounting ----------------------------------------------------


def _shape_of(key) -> str:
    """Compact printable rendering of a static kernel key — the "shape
    bucket" a compile event names (keys are tuples of ints/strings/op
    tuples; padding already bucketed them to powers of two)."""
    s = repr(key)
    return s if len(s) <= 200 else s[:197] + "..."


def note_compile(kind: str, key, wall_s: float,
                 cost: Optional[dict] = None) -> None:
    """A never-seen static shape's first dispatch: journal the typed
    ``kernel_compile`` event (trace-linked, so EXPLAIN ANALYZE and the
    slow log can attribute the stall), tick the per-kernel compile
    histogram + counter, and mark the paying query's ledger
    (``compile_hit``). ``wall_s`` is the first call's wall time — the
    honest upper bound on the XLA compile. ``cost`` optionally carries
    ``cost_analysis`` flops/bytes (see ``cost_analysis``)."""
    if not device_telemetry_enabled():
        return
    from ..utils import querystats

    hist = _M_COMPILE_SECONDS.get(kind)
    if hist is not None:
        hist.observe(wall_s)
    counter = _M_COMPILE.get((kind, "compile"))
    if counter is not None:
        counter.inc()
    querystats.record(compile_hit=1)
    from ..utils.events import record_event

    # NB record_event's own ``kind`` arg collides (the rule_kind
    # precedent): the kernel kind ships as ``kernel``.
    attrs: dict = {
        "kernel": kind,
        "shape": _shape_of(key),
        "wall_ms": round(wall_s * 1000.0, 3),
    }
    if cost:
        attrs.update({k: v for k, v in cost.items() if v is not None})
    record_event("kernel_compile", **attrs)


def device_free_bytes() -> Optional[int]:
    """HBM the first local device has left (its allocator's limit less the
    bytes in use, resident columns included), or None where the backend
    reports no limit (the CPU): what a program's temporaries must fit."""
    import jax

    stats = jax.local_devices()[0].memory_stats()
    if not stats or "bytes_limit" not in stats:
        return None
    return int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))


def refusal_of(exc: BaseException) -> Optional[str]:
    """The first line of what the device said when ``exc`` is its refusal
    of a program for memory (``RESOURCE_EXHAUSTED``: the compiler found
    no room in HBM for the program's temporaries, or the allocator none
    for its buffers), else None — any other failure is not a refusal."""
    import jax

    if not isinstance(exc, jax.errors.JaxRuntimeError):
        return None
    text = str(exc).strip()
    if "RESOURCE_EXHAUSTED" not in text:
        return None
    return text.splitlines()[0][:300]


def note_refusal(kind: str, impl: str, key, message: str) -> None:
    """The device refused a dispatch's program: journal the typed
    ``kernel_refused`` event (trace-linked like ``kernel_compile``) —
    ``kernel`` (the kind), ``impl``, ``shape`` and the device's first
    line. The caller marks the impl unusable and serves the request by
    another route."""
    from ..utils.events import record_event

    record_event(
        "kernel_refused", kernel=kind, impl=impl, shape=_shape_of(key),
        message=message,
    )


def note_dist_combine(nbytes: int) -> None:
    """One sharded dispatch handed each device's ``nbytes`` of partial
    aggregates to the collectives."""
    _M_COMBINE_BYTES.inc(nbytes)


def note_folded_chunks(n: int) -> None:
    """A fetched aggregate's scatter folded ``n`` of its row chunks."""
    if n:
        _M_FOLDED_CHUNKS.inc(n)


def note_block_lookups() -> None:
    """A dispatch's program read the per-series tables by block."""
    _M_BLOCK_LOOKUPS.inc()


def note_compile_cache_hit(kind: str) -> None:
    """A seen shape dispatched again: the compile cache served it."""
    if not device_telemetry_enabled():
        return
    counter = _M_COMPILE.get((kind, "hit"))
    if counter is not None:
        counter.inc()


def cost_analysis(jitfn, args=(), kwargs=None) -> Optional[dict]:
    """Best-effort XLA ``cost_analysis`` flops/bytes for a jit call.

    Opt-in (``HORAEDB_DEVICE_COST_ANALYSIS=1``): the AOT
    ``lower().compile()`` pays a SECOND compile of the shape, so it must
    never ride the default path — compile events carry kind/shape/wall
    regardless; flops/bytes only under the knob ("where available")."""
    import os

    if os.environ.get("HORAEDB_DEVICE_COST_ANALYSIS", "0") != "1":
        return None
    try:
        lowered = jitfn.lower(*args, **(kwargs or {}))
        ca = lowered.compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if not isinstance(ca, dict):
            return None
        out = {}
        for src, dst in (("flops", "flops"),
                         ("bytes accessed", "bytes_accessed")):
            v = ca.get(src)
            if v is not None:
                out[dst] = float(v)
        return out or None
    except Exception:
        return None


def compile_stats() -> dict:
    """Per-kernel compile/hit counts — the /debug/device compile block."""
    out = {}
    for kind in DEVICE_KERNEL_KINDS:
        compiles = _M_COMPILE[(kind, "compile")].value
        hits = _M_COMPILE[(kind, "hit")].value
        if compiles or hits:
            out[kind] = {"compiles": int(compiles), "hits": int(hits)}
    return out


def note_eviction(n: int = 1) -> None:
    """The scan cache evicted ``n`` entries under its HBM budget."""
    _M_EVICTIONS.inc(n)


# ---- HBM occupancy ---------------------------------------------------------

# Occupancy providers: anything holding device-resident state registers
# ITSELF (held weakly — a closed executor's cache drops out) and must
# expose ``snapshot_device() -> list[dict]`` (rows with table_name /
# column_name / component / dtype / bytes / rows / last_hit_age_ms /
# evictions). The scan cache registers at construction; the ROADMAP
# item-2 window state and item-4 encoded layouts plug in here.
_PROVIDERS: "weakref.WeakSet" = weakref.WeakSet()


def register_occupancy_provider(owner) -> None:
    """Track ``owner`` (weakly) as a device-residency source; it must
    expose ``snapshot_device() -> list[dict]``."""
    _PROVIDERS.add(owner)


def unregister_occupancy_provider(owner) -> None:
    """Drop ``owner`` from the inventory immediately — Connection.close
    calls this so a closed database's cache stops contributing rows the
    moment it closes instead of whenever GC collects it (the inventory
    is process-wide by design, like system.public.workload, but it must
    only merge LIVE sources). The gauges refresh forcibly afterwards:
    a close is a residency mutation like any eviction, and a parked
    gauge would report the freed bytes until the next cache serve."""
    _PROVIDERS.discard(owner)
    refresh_occupancy(force=True)


def _component_sums(rows: list[dict]) -> dict:
    """Byte totals per gauge component — THE one summing loop (the
    gauges, /debug/device totals, and the refresh fallback all use it;
    a new OCCUPANCY_COMPONENT lands in one place)."""
    sums = {c: 0 for c in ("column", "session", "stack")}
    for r in rows:
        c = r.get("component")
        if c in sums:
            sums[c] += int(r.get("bytes", 0))
    return sums


def device_inventory() -> list[dict]:
    """The full per-(table, column, dtype) residency inventory across
    every registered provider, with the resident-bytes gauges refreshed
    from what was just walked (so scrapes stay honest between queries)."""
    rows: list[dict] = []
    for p in list(_PROVIDERS):
        try:
            rows.extend(p.snapshot_device())
        except Exception:
            continue  # one sick provider must not dark the whole plane
    for c, v in _component_sums(rows).items():
        _M_RESIDENT[c].set(float(v))
    return rows


_last_refresh = 0.0


def refresh_occupancy(force: bool = False) -> None:
    """Recompute the resident-bytes gauges — the scan cache calls this
    after serving/mutations so the self-monitoring recorder scrapes
    fresh values. HOT-PATH cheap: providers exposing
    ``occupancy_bytes()`` are summed without materializing inventory
    rows, and un-forced refreshes are throttled to ~1/s (the recorder
    scrapes at 10s; per-query precision lives in the inventory reads,
    which always recompute live). Mutations that can be the LAST touch
    for a while (build, eviction, invalidate, bf16 drop) pass
    ``force=True`` so the throttle can never park a gauge on freed
    bytes forever."""
    global _last_refresh
    if not device_telemetry_enabled():
        return
    now = time.monotonic()
    if not force and now - _last_refresh < 1.0:
        return
    _last_refresh = now
    sums = {c: 0 for c in ("column", "session", "stack")}
    for p in list(_PROVIDERS):
        try:
            fast = getattr(p, "occupancy_bytes", None)
            per = fast() if fast is not None else _component_sums(
                p.snapshot_device()
            )
            for c, v in per.items():
                if c in sums:
                    sums[c] += int(v)
        except Exception:
            continue
    for c, g in _M_RESIDENT.items():
        g.set(float(sums[c]))


def occupancy_totals(rows: Optional[list[dict]] = None) -> dict:
    """Byte totals by component plus the grand total — the /debug/device
    summary block (``column`` is the scan cache's device_bytes truth)."""
    if rows is None:
        rows = device_inventory()
    out = _component_sums(rows)
    out["total"] = sum(out.values())
    return out
