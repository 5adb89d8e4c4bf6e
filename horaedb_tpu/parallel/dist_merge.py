"""Distributed merge-dedup: the compaction sort kernel under shard_map
(ref: the reference's compaction runs node-local,
analytic_engine/src/compaction/runner/local_runner.rs — a TPU pod can
instead split one merge across chips because the key space partitions
cleanly).

The same tsid-range chunking the single-chip pipeline uses
(engine/compaction.py _device_merge) maps chunks onto MESH DEVICES: every
duplicate key shares a chunk, so each device sorts + dedups its own slice
with ZERO collectives, and the chunk outputs concatenate in split order.
shard_map runs the per-device kernel body SPMD over the mesh — one
compile, n devices, each sorting bucket-padded u32 operands.
"""

from __future__ import annotations

import numpy as np


def dist_merge_dedup(
    mesh,
    tsid: np.ndarray,
    ts: np.ndarray,
    seq: np.ndarray,
    dedup: bool = True,
) -> np.ndarray:
    """Global row selection (indices into the input, in merged key order)
    for a k-way merge-dedup sharded over ``mesh``. Semantics match
    ops.merge_dedup.merge_dedup_permutation: sort by (tsid, ts, seq
    desc), keep the newest row per (tsid, ts) key."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..ops.encoding import next_pow2, split_u64
    from ..ops.merge_dedup import _pack_rest, fused32_sort_dedup

    n = len(tsid)
    n_dev = int(mesh.devices.size)
    if n == 0:
        return np.empty(0, dtype=np.int64)

    ts64 = ts.astype(np.int64, copy=False)
    seq64 = seq.astype(np.uint64, copy=False)

    # tsid-value chunk boundaries from a stride sample: duplicates of a
    # key can never straddle devices, which is what makes the merge
    # embarrassingly parallel.
    step = max(1, n // 65536)
    sample = np.sort(tsid[::step])
    splits = sample[
        [min(len(sample) - 1, (len(sample) * (i + 1)) // n_dev)
         for i in range(n_dev - 1)]
    ]
    cid = np.searchsorted(splits, tsid, side="right")
    idxs = [np.flatnonzero(cid == d) for d in range(n_dev)]
    bucket = next_pow2(max((len(i) for i in idxs), default=1), floor=256)

    # Same packed rest word (and span measurement) as the single-chip
    # fused kernel — ONE implementation; global spans so every device
    # shares one mask. Wide spans RAISE: callers must pre-chunk by time
    # (a segment-scoped merge always fits).
    kind, packed = _pack_rest(ts64, seq64)
    if kind != "f32":
        raise ValueError(
            "dist merge requires packed (ts, seq) spans <= 32 bits; "
            "pre-chunk by time first"
        )
    rest_full, rest_mask = packed

    U32_MAX = np.uint32(0xFFFFFFFF)
    op_hi = np.full((n_dev, bucket), U32_MAX, dtype=np.uint32)
    op_lo = np.full((n_dev, bucket), U32_MAX, dtype=np.uint32)
    op_rest = np.full((n_dev, bucket), U32_MAX, dtype=np.uint32)
    n_valid = np.zeros((n_dev, 1), dtype=np.int32)
    for d, idx in enumerate(idxs):
        k = len(idx)
        n_valid[d, 0] = k
        if k == 0:
            continue
        rev = idx[::-1]  # reversed + stable sort = newest input row wins
        hi, lo = split_u64(tsid[rev])
        op_hi[d, :k] = hi
        op_lo[d, :k] = lo
        op_rest[d, :k] = rest_full[rev]

    def body(hi, lo, rest, nv):
        perm, keep = fused32_sort_dedup(
            hi[0], lo[0], rest[0], jnp.uint32(rest_mask), nv[0, 0], dedup
        )
        return perm[None, :], keep[None, :]

    step_fn = jax.jit(
        shard_map(
            body,
            mesh=mesh,
            in_specs=(P("shard", None),) * 3 + (P("shard", None),),
            out_specs=(P("shard", None), P("shard", None)),
        )
    )
    perm, keep = jax.device_get(
        step_fn(
            *(jnp.asarray(a) for a in (op_hi, op_lo, op_rest)),
            jnp.asarray(n_valid),
        )
    )

    out = []
    for d, idx in enumerate(idxs):
        if len(idx):
            sel = perm[d][keep[d]]
            out.append(idx[sel])
    return (
        np.concatenate(out) if out else np.empty(0, dtype=np.int64)
    )
