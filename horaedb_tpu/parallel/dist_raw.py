"""Sharded raw reads: fused filter + top-k / selection over a mesh.

When a table's scan-cache entry is sharded across the chip mesh
(scan_cache places the big row arrays with ``P("shard")``), raw reads
run the SAME kernel bodies as the single-device path (ops/scan_topk)
per shard under ``shard_map``:

- **top-k**: each device computes its local top-k (k slots each — the
  global top-k is necessarily a subset of the union of per-shard
  top-ks), converts local row offsets to GLOBAL resident row ids via
  ``axis_index`` (shards are contiguous row blocks), and ships k keys +
  k ids home; the host merges n_dev sorted k-lists (tiny) into the
  global top-k with the same key-desc/rowid-asc tie order.
- **selection**: each device compacts its passing rows into its own
  bounded buffer; buffers concatenate in shard order == global resident
  (series, ts) order, so the host just stitches valid prefixes.

Compiled steps live in parallel/dist_agg's LRU-bounded step cache
(``cached_step`` — one discipline, one bound, distinct key spaces).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.scan_agg import encode_filter_ops
from ..ops.scan_topk import _I32_MIN, RawScanSpec, raw_select_body, raw_topk_body
from .dist_agg import cached_step

SHARD_AXIS = "shard"

_IN_SPECS = (
    P(SHARD_AXIS),  # series codes (rows)
    P(SHARD_AXIS),  # relative timestamps (rows)
    P(None, SHARD_AXIS),  # value columns (fields, rows)
    P(None),  # series allow list (replicated)
    P(None),  # filter literals
    P(), P(),  # time-range scalars
    P(), P(),  # bisection key-bound seeds (topk; select ignores)
)


def make_dist_raw_topk(mesh: Mesh, spec: RawScanSpec) -> Callable:
    """step(codes, ts_rel, values, allow, literals, lo, hi) ->
    (keys int32[n_dev*k], global row idx int32[n_dev*k])."""
    static_filters = encode_filter_ops(spec.numeric_filters)
    key = ("raw_topk", spec.k, spec.descending, spec.key_is_ts,
           spec.key_field, static_filters)

    def build():
        def per_shard(codes, ts_rel, values, allow, literals, lo, hi,
                      key_lo, key_hi):
            vals, idx = raw_topk_body(
                codes, ts_rel, values, allow, literals, lo, hi,
                key_lo, key_hi,
                k=spec.k, descending=spec.descending,
                key_is_ts=spec.key_is_ts, key_field=spec.key_field,
                numeric_filters=static_filters,
            )
            offset = jax.lax.axis_index(SHARD_AXIS).astype(jnp.int32)
            return vals, idx + offset * jnp.int32(codes.shape[0])

        return jax.jit(
            shard_map(
                per_shard, mesh=mesh, in_specs=_IN_SPECS,
                out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
                # the bisection while_loop has no replication rule; every
                # output is explicitly sharded, so the check adds nothing
                check_vma=False,
            )
        )

    return cached_step((mesh, key), build)


def make_dist_raw_select(mesh: Mesh, spec: RawScanSpec) -> Callable:
    """step(codes, ts_rel, values, allow, literals, lo, hi) ->
    (row idx int32[n_dev*slots], per-shard counts int32[n_dev])."""
    static_filters = encode_filter_ops(spec.numeric_filters)
    key = ("raw_select", spec.select_slots, static_filters)

    def build():
        def per_shard(codes, ts_rel, values, allow, literals, lo, hi,
                      _key_lo, _key_hi):
            out, count = raw_select_body(
                codes, ts_rel, values, allow, literals, lo, hi,
                select_slots=spec.select_slots,
                numeric_filters=static_filters,
            )
            offset = jax.lax.axis_index(SHARD_AXIS).astype(jnp.int32)
            # global row ids; -1 pad slots stay -1
            out = jnp.where(
                out >= 0, out + offset * jnp.int32(codes.shape[0]), out
            )
            return out, count.reshape(1)

        return jax.jit(
            shard_map(
                per_shard, mesh=mesh, in_specs=_IN_SPECS,
                out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
            )
        )

    return cached_step((mesh, key), build)


def dist_raw_topk(
    mesh: Mesh, spec: RawScanSpec, codes, ts_rel, values, allow,
    literals, lo_rel: int, hi_rel: int, key_lo: int, key_hi: int,
    need: int,
) -> np.ndarray:
    """Run the sharded top-k and merge the per-shard k-lists on host.

    -> global resident row ids of the top-``need`` passing rows,
    selected with the single-device tie rule (key first, then smaller
    resident row id). ``need`` may EXCEED ``spec.k``: the executor
    clamps per-shard k to the shard length (a shard shorter than the
    request contributes all its rows), so the merged union holds up to
    n_dev * k candidates and must be cut at the REQUESTED count, never
    at the shard-clamped k."""
    step = make_dist_raw_topk(mesh, spec)
    keys, idx = jax.device_get(
        step(codes, ts_rel, values, allow,
             jnp.asarray(np.asarray(literals, dtype=np.float32)),
             jnp.int32(lo_rel), jnp.int32(hi_rel),
             jnp.int32(key_lo), jnp.int32(key_hi))
    )
    keys = np.asarray(keys)
    idx = np.asarray(idx)
    valid = keys != _I32_MIN
    keys, idx = keys[valid], idx[valid]
    # merge n_dev k-lists: key desc, row id asc on ties (lexsort is
    # ascending and stable; negate keys, secondary key = row id)
    order = np.lexsort((idx, -keys.astype(np.int64)))
    return idx[order[:need]]


def dist_raw_select(
    mesh: Mesh, spec: RawScanSpec, codes, ts_rel, values, allow,
    literals, lo_rel: int, hi_rel: int,
) -> tuple[np.ndarray, int]:
    """Run the sharded selection; -> (global row ids in resident order,
    total passing count). Counts can exceed a shard's buffer only if the
    caller's candidate bound was wrong — it returns the truth so the
    executor can fall back instead of serving a truncated result."""
    step = make_dist_raw_select(mesh, spec)
    out, counts = jax.device_get(
        step(codes, ts_rel, values, allow,
             jnp.asarray(np.asarray(literals, dtype=np.float32)),
             jnp.int32(lo_rel), jnp.int32(hi_rel),
             jnp.int32(0), jnp.int32(0))
    )
    out = np.asarray(out).reshape(-1, spec.select_slots)
    counts = np.asarray(counts)
    total = int(counts.sum())
    if (counts > spec.select_slots).any():
        return np.empty(0, dtype=np.int32), total
    parts = [
        out[d, : int(counts[d])] for d in range(len(counts)) if counts[d]
    ]
    idx = (
        np.concatenate(parts) if parts else np.empty(0, dtype=np.int32)
    )
    return idx, total
