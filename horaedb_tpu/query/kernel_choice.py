"""Which segment kernel runs: the one place that decides.

The device group-by has two segment-reduction kernels the chip has timed
(ops/scan_agg.py: ``mxu`` one-hot matmul, ``scatter`` segment ops) and a
plain reduction for one segment (``single``). The executor's direct,
cached and dist paths and the partial-agg push-down all ask here, through
three calls: ``choose`` names a concrete impl for a spec (or None: the
host serves), ``finish`` closes the dispatch it named, ``refused`` takes
an impl the device had no room for out of the shape's candidates. The
kernels below take the name as given; nothing above or beside this
module re-derives the rule.

``choose`` answers from a learned router over the candidates that fit the
device (same estimates and probe schedule as ``PathRouter``, one level
down: keyed by (plan shape, segment-count bucket)), seeded by the static
rule — which is also the answer where nothing is routed (one segment).
"""

from __future__ import annotations

import dataclasses

import jax

from ..obs import device
from ..obs.decisions import record_decision, resolve_decision
from ..ops.scan_agg import segment_temp_bytes
from ..utils import querystats
from .path_router import _ProbeSchedule

# The static crossover: on the TPU, segment counts at or below it start on
# the MXU one-hot matmul, above it on the scatter (PERF.md §5: 64 segments
# mxu_sel 2.494 / scatter_sel 2.667 ms; 16,384 segments scatter 158.5 / mxu
# 480.4 ms).
MXU_MAX_SEGMENTS = 8192
# How far past the crossover the one-hot is still worth a PROBE: its work is
# O(N * n_seg), so beyond a bounded extrapolation a single probe could cost
# seconds. The 4x is MXU-calibrated; without a matrix unit the one-hot
# bites orders of magnitude sooner.
_MXU_PROBE_FACTOR = 4
_ONE_HOT_MAX_SEGMENTS_NO_MXU = 256


def static_kernel(n_seg: int) -> str:
    """THE static rule: the router's seed for a never-measured shape, and
    the answer where nothing is routed. One segment (a global aggregate) is
    four streaming reduces — scatter's scalarized segment ops and a width-1
    one-hot matmul both waste passes there."""
    if n_seg <= 1:
        return "single"
    if jax.default_backend() == "tpu" and n_seg <= MXU_MAX_SEGMENTS:
        return "mxu"
    return "scatter"


def candidate_kernels(n_seg: int, n_rows: int, n_fields: int = 0,
                      need_minmax: bool = False) -> tuple:
    """Impls worth PROBING for this shape. Routing must never schedule a
    probe that is catastrophically wrong by construction (the one-hot past
    its extrapolation bound), nor one the device would refuse: an impl
    whose temporaries (``segment_temp_bytes``) exceed the device's free
    memory is not offered — () when none fits, and the host serves the
    query."""
    cands = ["scatter"]
    if n_seg <= (
        _MXU_PROBE_FACTOR * MXU_MAX_SEGMENTS
        if jax.default_backend() == "tpu"
        else _ONE_HOT_MAX_SEGMENTS_NO_MXU
    ):
        cands.append("mxu")
    free = device.device_free_bytes()
    if free is not None:
        cands = [
            k for k in cands
            if segment_temp_bytes(k, n_rows, n_seg, n_fields, need_minmax) <= free
        ]
    return tuple(cands)


class KernelRouter(_ProbeSchedule):
    """Per-(plan-shape, segment-bucket) EWMA over the segment impls.

    Same discipline as PathRouter: warm each candidate (dropping its
    compile-tainted first sample), serve the measured winner, re-probe
    each loser as its own budget of serving time allows, the most overdue
    first, so the choice adapts when conditions change."""

    router = "kernel"

    def choose(self, key, seed: str, candidates: tuple):
        """-> (the impl to dispatch this call with, its estimate or None
        before its first clean sample); (None, None) when there is no impl
        to offer: ``candidates`` is empty, or the device has refused every
        one of them for this key (``refuse``)."""
        with self._lock:
            st = self._touch(key)
            refused = st.get("refused", ())
            candidates = tuple(k for k in candidates if k not in refused)
            if not candidates:
                return None, None
            times = st["t"]
            for k in [seed] + [k for k in candidates if k != seed]:
                # two samples each: the first pays jit trace+compile and
                # is dropped by record() — judging needs a clean one
                if k in candidates and k not in times:
                    return k, None
            winner = min(candidates, key=times.get)
            losers = [k for k in candidates if k != winner]
            impl = self._due_probe(st, losers) or winner
            return impl, times[impl]

    def record(self, key, kernel: str, seconds: float) -> None:
        """Fold a dispatch latency in (``_fold``); the first sample of each
        impl (compile-tainted) only counts, never judges."""
        with self._lock:
            st = self._touch(key)
            warmed = st.setdefault("warmed", set())
            if kernel not in warmed or kernel in st.get("refused", ()):
                # compile-tainted; or refused while this one was in flight
                warmed.add(kernel)
                return
            self._fold(st, kernel, seconds)

    def refuse(self, key, kernel: str) -> None:
        """The device refused ``kernel``'s program for this key (no room in
        HBM): never offer it for the key again, and drop its estimate —
        a route that cannot serve is no winner for ``_fold`` to defer to."""
        with self._lock:
            st = self._touch(key)
            st.setdefault("refused", set()).add(kernel)
            for per_route in (st["t"], st["n"], st["since"]):
                per_route.pop(kernel, None)

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
# consumer (direct device path, cached path, dist-agg step, partial-agg
# push-down) folds into and serves from the same history.
KERNEL_ROUTER = KernelRouter()


def choose(shape_key, spec, n_rows: int):
    """-> (``spec`` with a concrete ``segment_impl``, token for ``finish``
    / ``refused``). The token is None where nothing is routed (one
    segment: the static rule answers); the spec is None when no impl can
    be offered — none fits the device's free memory (``candidate_kernels``)
    or the device has refused every one for this key — and the host
    serves the query."""
    n_seg = spec.n_groups * spec.n_buckets
    if n_seg <= 1:
        return dataclasses.replace(spec, segment_impl=static_kernel(n_seg)), None
    key = (shape_key, n_seg.bit_length())
    candidates = candidate_kernels(
        n_seg, n_rows, spec.n_agg_fields, spec.need_minmax
    )
    impl, predicted = KERNEL_ROUTER.choose(key, static_kernel(n_seg), candidates)
    if impl is None:
        return None, None
    # Decision plane: journal the pick with the EWMA's own prediction of
    # what this impl costs for this shape (None until the impl has a
    # clean sample — those picks resolve ungraded). The id rides the
    # token to ``finish``, where the same amortized dispatch seconds
    # that feed the EWMA also grade the prediction.
    dec_id = record_decision(
        "kernel_router",
        key=f"{shape_key[0] if shape_key else ''}#b{n_seg.bit_length()}",
        choice=impl,
        features={"n_seg": n_seg, "candidates": list(candidates)},
        predicted=predicted,
    )
    return dataclasses.replace(spec, segment_impl=impl), (key, impl, dec_id)


def finish(token, spec, m: dict, state, seconds: float) -> None:
    """Close one aggregation dispatch: feed the router's EWMA, stamp the
    metric tree, the ledger ``kernel`` field, and the
    horaedb_agg_kernel_total family."""
    live = int((state.counts > 0).sum())
    if token is not None:
        key, impl, dec_id = token
        if live > 0:
            KERNEL_ROUTER.record(key, impl, seconds)
            resolve_decision(
                dec_id, actual=seconds, outcome="served",
                loop="kernel_router",
            )
        else:
            # Degenerate dispatches (empty time range, filter matching
            # nothing) stay out of the EWMA: their near-zero latency would
            # make whichever impl served them look unbeatable under the
            # min-biased estimator. The decision closes (no leaked pending
            # entry) but must not grade the prediction.
            resolve_decision(
                dec_id, actual=seconds, outcome="degenerate",
                loop="kernel_router", calibrate=False,
            )
    m["kernel"] = spec.segment_impl
    querystats.note_agg_kernel(spec.segment_impl, segments=live)


def refused(token, seconds: float) -> None:
    """The device refused the program of the impl ``choose`` named: never
    offer it for the shape again, and close its journal entry ungraded.
    The caller chooses again (the next candidate, or None: the host)."""
    key, impl, dec_id = token
    KERNEL_ROUTER.refuse(key, impl)
    resolve_decision(
        dec_id, actual=seconds, outcome="refused",
        loop="kernel_router", calibrate=False,
    )
