"""Sharded scan/aggregate: the distributed query step
(ref: df_engine_extensions/src/dist_sql_query — partial agg pushed to data
nodes, final agg at the coordinator; resolver.rs:76-120).

TPU-native re-expression: ``shard_map`` over a 1-D mesh axis ``"shard"``.
Each device runs the SAME fused scan/agg body on its row shard (rows are
sharded along axis 0 / the trailing row axis of values), then the
aggregation monoid combines across devices with XLA collectives:

    counts, sums -> psum        mins -> pmin        maxs -> pmax

which ride ICI inside a slice and DCN across slices — XLA picks the
collective implementation; the program is identical from 1 to N devices.
"""

from __future__ import annotations

import functools
import threading
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.encoding import PaddedBatch
from ..ops.scan_agg import (
    AggState,
    ScanAggSpec,
    cached_scan_agg_body,
    coerce_literals,
    concrete_impl,
    encode_filter_ops,
    scan_agg_body,
    state_to_host,
)

SHARD_AXIS = "shard"

# Compiled steps keyed by (mesh, spec): jax.jit caches by function identity,
# so rebuilding the shard_map closure per call would re-compile every time.
# LRU-bounded with the same discipline (and the same bound) as
# PathRouter.MAX_KEYS — distinct query shapes must not grow it without
# limit over a server's lifetime; dict insertion order is the recency
# order, re-inserting moves a key to the back.
_STEP_CACHE: dict = {}
_STEP_LOCK = threading.Lock()


def _step_cache_max() -> int:
    from ..query.path_router import MAX_KEYS

    return MAX_KEYS


def _combine(state, need_minmax: bool):
    """The aggregation monoid as mesh collectives (final aggregate). Without
    ``need_minmax`` the body's mins and maxs are zeros on every device and
    stay as they are. The count of folded scatter steps rides the counts'
    all-reduce, one int32 more."""
    counts, sums, mins, maxs, folded = state
    with jax.named_scope("dist_combine"):
        both = jax.lax.psum(
            jnp.concatenate([counts.reshape(-1), folded[None]]), SHARD_AXIS
        )
        counts, folded = both[:-1].reshape(counts.shape), both[-1]
        sums = jax.lax.psum(sums, SHARD_AXIS)
        if need_minmax:
            mins = jax.lax.pmin(mins, SHARD_AXIS)
            maxs = jax.lax.pmax(maxs, SHARD_AXIS)
    return counts, sums, mins, maxs, folded


def combine_bytes(spec: ScanAggSpec) -> int:
    """Bytes each device hands ``_combine``'s collectives in one dispatch of
    ``spec``, from the static shapes: int32 counts per segment and an f32
    per segment and field for the sums (and for mins and maxs, if wanted);
    not the one int32 of folded scatter steps beside the counts."""
    n_seg = spec.n_groups * spec.n_buckets
    reductions = 3 if spec.need_minmax else 1
    return 4 * n_seg * (1 + reductions * spec.n_agg_fields)


def dist_program_name(tag: str, segment_impl: str) -> str:
    """What the device trace calls a sharded step: ``XLA Modules`` reads
    ``jit_cached_dist_scatter(...)`` (``tag`` ``cached``: over the resident
    columns; ``fused``: over an uploaded batch), as ``packed_program_name``
    names the one-device programs."""
    return f"{tag}_dist_{concrete_impl(segment_impl)}"


def cached_step(cache_key, build) -> Callable:
    """THE compiled-step LRU: get-or-build under the lock, bounded at
    PathRouter.MAX_KEYS, dict insertion order = recency. One discipline
    for every shard_map step cache (the agg steps here, the raw-read
    steps in parallel/dist_raw) — distinct key spaces share one bound."""
    with _STEP_LOCK:
        cached = _STEP_CACHE.pop(cache_key, None)
        if cached is not None:
            _STEP_CACHE[cache_key] = cached  # LRU touch
            return cached
    step = build()
    with _STEP_LOCK:
        while len(_STEP_CACHE) >= _step_cache_max():
            _STEP_CACHE.pop(next(iter(_STEP_CACHE)))
        _STEP_CACHE[cache_key] = step
    return step


def _build_step(mesh: Mesh, spec: ScanAggSpec, tag: str, body, in_specs,
                **layouts) -> Callable:
    """shard_map(body)+combine, jitted under ``dist_program_name`` and cached
    per (mesh, spec, tag, layouts). ``spec.segment_impl`` is the chooser's
    concrete name: it keys the step cache and the jit trace. ``layouts``:
    static layout descriptors other than ``body``'s defaults, handed to it
    as they are."""
    name = dist_program_name(tag, spec.segment_impl)

    def build():
        static_filters = encode_filter_ops(spec.numeric_filters)

        def per_shard(*args):
            return _combine(
                body(
                    *args,
                    n_groups=spec.n_groups,
                    n_buckets=spec.n_buckets,
                    n_agg_fields=spec.n_agg_fields,
                    numeric_filters=static_filters,
                    need_minmax=spec.need_minmax,
                    segment_impl=spec.segment_impl,
                    **layouts,
                ),
                spec.need_minmax,
            )

        sharded = shard_map(
            per_shard, mesh=mesh, in_specs=in_specs,
            out_specs=(P(), P(), P(), P(), P()),
            # the scatter's row chunks run in a lax.scan whose carried
            # accumulators start replicated and come back varying over the
            # mesh axis: no replication rule; _combine replicates every
            # output itself, so the check adds nothing
            check_vma=False,
        )

        def step(*args):
            return sharded(*args)

        step.__name__ = step.__qualname__ = name
        return jax.jit(step)

    return cached_step((mesh, spec, tag, *sorted(layouts.items())), build)


def make_dist_scan_agg(mesh: Mesh, spec: ScanAggSpec) -> Callable:
    """Compile (or fetch cached) the sharded scan/agg step for ``spec``.

    Returns ``step(group_codes, bucket_ids, mask, values, literals)`` where
    row-dimension inputs are sharded over the mesh axis and the output
    aggregate state is replicated (fully combined) on every device.
    """
    return _build_step(
        mesh,
        spec,
        "fused",
        scan_agg_body,
        in_specs=(
            P(SHARD_AXIS),  # group codes (rows)
            P(SHARD_AXIS),  # bucket ids (rows)
            P(SHARD_AXIS),  # mask (rows)
            P(None, SHARD_AXIS),  # value columns (fields, rows)
            P(None),  # filter literals
        ),
    )


def make_cached_dist_scan_agg(
    mesh: Mesh, spec: ScanAggSpec, block_width: int | None = None
) -> Callable:
    """Sharded version of the HBM-resident cached kernel.

    The cache's big per-row arrays (series codes, relative timestamps,
    value columns) live SHARDED across the mesh (scan_cache places them
    with ``P("shard")``); per-query small inputs (series→group map, allow
    list, literals, time scalars) are replicated. Each device aggregates
    its row shard, then the monoid combines via collectives — the default
    serving path on a multi-chip mesh, not a demo path.

    ``block_width``: the entry's ``series_block_width``. With it each
    device reads the series→group map and the allow list through its
    codes' 128-row blocks (``encoding.block_series``); None gathers both
    tables per row.
    """
    layouts = {} if block_width is None else {
        "series_layout": ("blocked", block_width)
    }
    return _build_step(
        mesh,
        spec,
        "cached",
        cached_scan_agg_body,
        in_specs=(
            P(SHARD_AXIS),  # series codes (rows)
            P(SHARD_AXIS),  # relative timestamps (rows)
            P(None, SHARD_AXIS),  # value columns (fields, rows)
            P(None),  # series -> group map (replicated)
            P(None),  # series allow list (replicated)
            P(None),  # filter literals
            P(), P(), P(), P(),  # time-range / bucket scalars
        ),
        **layouts,
    )


def dist_scan_aggregate(
    mesh: Mesh,
    batch: PaddedBatch,
    spec: ScanAggSpec,
    filter_literals=(),
) -> AggState:
    """Convenience wrapper: pad the batch to a multiple of the mesh size,
    run the sharded step, return host-side combined partials."""
    n_dev = mesh.devices.size
    padded = batch.padded_len
    group_codes, bucket_ids, mask, values = (
        batch.group_codes, batch.bucket_ids, batch.mask, batch.values,
    )
    rem = padded % n_dev
    if rem:
        # Shape buckets are powers of two, so this only triggers on
        # non-power-of-two meshes. Pad rows are masked out, so they never
        # touch the aggregates.
        extra = n_dev - rem
        group_codes = np.pad(group_codes, (0, extra))
        bucket_ids = np.pad(bucket_ids, (0, extra))
        mask = np.pad(mask, (0, extra))  # False fill
        values = np.pad(values, ((0, 0), (0, extra)))
    step = make_dist_scan_agg(mesh, spec)
    import time as _time

    from ..obs.device import note_dist_combine, timed_dispatch
    from ..utils.querystats import note_kernel_dispatch

    t0 = _time.perf_counter()
    out = timed_dispatch(
        "fused_dist",
        lambda: step(
            jnp.asarray(group_codes),
            jnp.asarray(bucket_ids),
            jnp.asarray(mask),
            jnp.asarray(values),
            coerce_literals(filter_literals),
        ),
    )
    note_dist_combine(combine_bytes(spec))
    state = state_to_host(*out)
    # Compile accounting for the sharded fused path — a first-sighting
    # shard_map compile is a MULTI-SECOND stall on real chips and must
    # journal/mark compile_hit like every other dispatch point (the
    # single-device path accounts inside scan_aggregate; this wrapper is
    # the dist equivalent). ``spec`` is the same static key that keys
    # the step cache; ``values.shape`` carries the padded batch bucket.
    note_kernel_dispatch(
        ("fused-dist", int(n_dev), values.shape, spec),
        _time.perf_counter() - t0,
        kind="fused_dist",
    )
    return state
