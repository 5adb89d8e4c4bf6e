"""Device k-way merge + dedup as one sort kernel
(ref: analytic_engine/src/row_iter/{merge.rs,dedup.rs} and the compaction
runner's merge loop — the BASELINE.json "k-way merge-dedup lifted onto TPU").

The reference merges k sorted runs with a BinaryHeap, comparing rows one at
a time. On TPU the same job is a data-parallel sort: concatenate the runs,
sort by (primary key asc, sequence desc), and collapse duplicate keys with
a shift-compare mask. ``lax.sort`` lowers to an efficient multi-operand
device sort, and the dedup mask is one vectorized compare — no per-row
control flow anywhere.

Operand count is the whole game: XLA's variadic sort cost (and the
upload) scales with the number of u32 words it
carries per row. The r4 kernel carried 8; a merge's actual entropy is far
smaller — timestamps span one segment window (~2^23 ms) and sequences span
the input files (~2^7) — so the hot path packs ``(ts - ts_min, seq_max -
seq)`` into ONE u32 word picked by measured bit widths, keeps the 64-bit
tsid hash as an (hi, lo) pair, and sorts 4 operands: tsid_hi, tsid_lo,
packed rest, row index. The two wider fallbacks (u64 rest pair; the
original fully-general split of every column) engage only when the
measured spans don't fit.

64-bit keys without enabling x64: values are split into order-preserving
(hi, lo) uint32 pairs on host (ops.encoding.split_*), and the device sorts
the pair lexicographically.

Newest-wins ties without a tie-break operand: the input is REVERSED on
host before padding, and the sort is stable — among rows with identical
(key, seq) the LAST input row sorts first, which is what the reference's
overwrite-in-order memtable semantics require. Pad rows carry all-ones
keys (sort to the tail) and are identified exactly by their sorted row
index >= n_valid — no dedicated is_pad operand, and a (vanishingly
unlikely) real row whose key words are all ones still wins its tie against
the pads because it precedes them in input order.
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from .encoding import pad_to_bucket, shape_bucket, split_i64_sortable, split_u64

_U32_MAX = np.uint32(0xFFFFFFFF)

# Kernel-shape keys ((kind, bucket, dedup) — all jit cache keys) whose sort
# kernel has finished compiling, and those with a compile in flight. A
# multi-operand u32 sort can take MINUTES to compile for a
# TPU — a foreground read must never eat that stall, so callers check
# merge_dedup_ready() and fall back to the host merge until the background
# compile lands. Failed compiles back off _FAIL_RETRY_S before retrying.
_ready: set[tuple] = set()
_compiling: set[tuple] = set()
_failed_at: dict[tuple, float] = {}
_compile_lock = threading.Lock()
_FAIL_RETRY_S = 60.0


def _compile_key(key: tuple) -> None:
    kind, bucket, dedup = key
    try:
        zeros = jnp.zeros(bucket, dtype=jnp.uint32)
        if kind == "rk":
            out = _ranked_kernel(
                zeros, zeros, jnp.uint32(0xFFFFFFFF), jnp.uint32(0xFFFFFFFF),
                jnp.int32(bucket), dedup=dedup,
            )
        elif kind == "f32":
            out = _fused32_kernel(
                zeros, zeros, zeros, jnp.uint32(0xFFFFFFFF),
                jnp.int32(bucket), dedup=dedup,
            )
        elif kind == "f64":
            out = _fused64_kernel(
                zeros, zeros, zeros, zeros, jnp.uint32(0xFFFFFFFF),
                jnp.uint32(0xFFFFFFFF), jnp.int32(bucket), dedup=dedup,
            )
        else:
            out = _general_kernel(*([zeros] * 7), dedup=dedup)
        jax.block_until_ready(out)
        with _compile_lock:
            _ready.add(key)
            _failed_at.pop(key, None)
    except Exception:
        import logging
        import time

        logging.getLogger(__name__).exception(
            "background merge-kernel compile failed (%s bucket=%d dedup=%s); "
            "retrying after %.0fs", kind, bucket, dedup, _FAIL_RETRY_S,
        )
        with _compile_lock:
            _failed_at[key] = time.time()
    finally:
        with _compile_lock:
            _compiling.discard(key)


def _ready_or_start_compile(key: tuple) -> bool:
    """True when ``key``'s kernel is compiled; otherwise kicks off (at
    most one) background compile for it and returns False."""
    import time

    with _compile_lock:
        if key in _ready:
            return True
        failed = _failed_at.get(key)
        if failed is not None and time.time() - failed < _FAIL_RETRY_S:
            return False
        if key not in _compiling:
            _compiling.add(key)
            threading.Thread(
                target=_compile_key, args=(key,), daemon=True
            ).start()
        return False


def merge_dedup_ready(n: int, dedup: bool = True) -> bool:
    """Advisory pre-warm of the hot-path (packed u32) kernel for
    ``n``-row merges. Foreground callers that must never eat a compile
    stall should ALSO pass ``require_ready=True`` to
    merge_dedup_permutation — the data's measured spans may route to a
    wider kernel than the one this warms."""
    return _ready_or_start_compile(("f32", shape_bucket(n), dedup))


@functools.partial(jax.jit, static_argnames=("dedup",))
def _ranked_kernel(key_hi, key_lo, mask_hi, mask_lo, n_valid, *, dedup: bool):
    """Fastest path: the WHOLE (tsid-rank, ts, seq desc) key packed into
    one u64 (hi, lo) pair — 3 operands, 2 keys, UNSTABLE sort. Callers
    must guarantee composite uniqueness (deduped sorted runs with
    distinct per-file sequences — compaction inputs): with unique keys an
    unstable sort is deterministic, and no tie-break operand or input
    reversal is needed. ``mask_*`` zero the seq bits for the dedup
    compare. Pads carry all-ones keys (> any real composite, which fits
    63 bits) and are identified by sorted index >= n_valid."""
    n = key_hi.shape[0]
    iota = jax.lax.iota(jnp.uint32, n)
    s_hi, s_lo, s_idx = jax.lax.sort(
        (key_hi, key_lo, iota), num_keys=2, is_stable=False
    )
    perm = s_idx.astype(jnp.int32)
    if dedup:
        k_hi = s_hi & mask_hi
        k_lo = s_lo & mask_lo
        same = (k_hi[1:] == k_hi[:-1]) & (k_lo[1:] == k_lo[:-1])
        keep = jnp.concatenate([jnp.ones(1, dtype=jnp.bool_), ~same])
    else:
        keep = jnp.ones(n, dtype=jnp.bool_)
    keep = keep & (s_idx < n_valid.astype(jnp.uint32))
    return perm, keep


def fused32_sort_dedup(tsid_hi, tsid_lo, rest, rest_mask, n_valid, dedup: bool):
    """Pure-jnp body: sort by (tsid, packed (ts, seq desc)) — 4 operands,
    3 keys. Shared by the jitted single-device kernel below and the
    shard_map distributed merge (parallel/dist_merge.py), so the
    reversal/pad/mask contract lives in exactly one place.

    Input arrives REVERSED (last original row first); the stable sort
    therefore resolves exact-duplicate rows to the newest input row, and
    ``perm`` recovers original indices as ``n_valid - 1 - sorted_idx``.
    ``rest_mask`` zeroes the seq bits so the dedup compare sees (ts) only.
    """
    n = tsid_hi.shape[0]
    iota = jax.lax.iota(jnp.uint32, n)
    s_hi, s_lo, s_rest, s_idx = jax.lax.sort(
        (tsid_hi, tsid_lo, rest, iota), num_keys=3, is_stable=True
    )
    perm = n_valid - jnp.int32(1) - s_idx.astype(jnp.int32)
    if dedup:
        key_rest = s_rest & rest_mask
        same = (
            (s_hi[1:] == s_hi[:-1])
            & (s_lo[1:] == s_lo[:-1])
            & (key_rest[1:] == key_rest[:-1])
        )
        keep = jnp.concatenate([jnp.ones(1, dtype=jnp.bool_), ~same])
    else:
        keep = jnp.ones(n, dtype=jnp.bool_)
    # Pads were appended after the reversed real rows: sorted idx >= n_valid
    # identifies them exactly (their all-ones keys put them in the tail).
    keep = keep & (s_idx < n_valid.astype(jnp.uint32))
    return perm, keep


@functools.partial(jax.jit, static_argnames=("dedup",))
def _fused32_kernel(tsid_hi, tsid_lo, rest, rest_mask, n_valid, *, dedup: bool):
    return fused32_sort_dedup(tsid_hi, tsid_lo, rest, rest_mask, n_valid, dedup)


@functools.partial(jax.jit, static_argnames=("dedup",))
def _fused64_kernel(
    tsid_hi, tsid_lo, rest_hi, rest_lo, mask_hi, mask_lo, n_valid, *, dedup: bool
):
    """Wide-span variant: packed (ts, seq desc) as a u64 (hi, lo) pair —
    5 operands, 4 keys. Same reversal/stability contract as _fused32."""
    n = tsid_hi.shape[0]
    iota = jax.lax.iota(jnp.uint32, n)
    s_hi, s_lo, s_rhi, s_rlo, s_idx = jax.lax.sort(
        (tsid_hi, tsid_lo, rest_hi, rest_lo, iota), num_keys=4, is_stable=True
    )
    perm = n_valid - jnp.int32(1) - s_idx.astype(jnp.int32)
    if dedup:
        k_rhi = s_rhi & mask_hi
        k_rlo = s_rlo & mask_lo
        same = (
            (s_hi[1:] == s_hi[:-1])
            & (s_lo[1:] == s_lo[:-1])
            & (k_rhi[1:] == k_rhi[:-1])
            & (k_rlo[1:] == k_rlo[:-1])
        )
        keep = jnp.concatenate([jnp.ones(1, dtype=jnp.bool_), ~same])
    else:
        keep = jnp.ones(n, dtype=jnp.bool_)
    keep = keep & (s_idx < n_valid.astype(jnp.uint32))
    return perm, keep


@functools.partial(jax.jit, static_argnames=("dedup",))
def _general_kernel(
    is_pad, tsid_hi, tsid_lo, ts_hi, ts_lo, negseq_hi, negseq_lo, *, dedup: bool
):
    """Fully-general fallback (every 64-bit column split, 8 operands —
    the r4 kernel): engages only when the measured ts/seq spans exceed 64
    packed bits, which a segment-scoped merge doesn't produce."""
    n = is_pad.shape[0]
    iota = jax.lax.iota(jnp.uint32, n)
    # Ties on (key, seq) — duplicate keys in ONE write batch share a WAL
    # sequence — resolve to the LAST input row (row order wins, matching
    # the reference's memtable overwrite-in-order semantics): sort the
    # NEGATED index as the final key, recover perm as its complement.
    negidx = jnp.uint32(n - 1) - iota
    sorted_ops = jax.lax.sort(
        (is_pad, tsid_hi, tsid_lo, ts_hi, ts_lo, negseq_hi, negseq_lo, negidx),
        num_keys=8,
        is_stable=True,
    )
    s_pad, s_tsid_hi, s_tsid_lo, s_ts_hi, s_ts_lo, _, _, s_negidx = sorted_ops
    perm = (jnp.uint32(n - 1) - s_negidx).astype(jnp.int32)
    if dedup:
        same = (
            (s_tsid_hi[1:] == s_tsid_hi[:-1])
            & (s_tsid_lo[1:] == s_tsid_lo[:-1])
            & (s_ts_hi[1:] == s_ts_hi[:-1])
            & (s_ts_lo[1:] == s_ts_lo[:-1])
        )
        keep = jnp.concatenate([jnp.ones(1, dtype=jnp.bool_), ~same])
    else:
        keep = jnp.ones(n, dtype=jnp.bool_)
    keep = keep & (s_pad == 0)
    return perm, keep


def _pack_rest(ts64: np.ndarray, seq64: np.ndarray):
    """Measure ts/seq spans and pack both into the narrowest key that
    preserves (ts asc, seq desc) order. Returns (kind, payload):

    - ("f32", (rest_u32, mask_u32))          spans fit 32 bits together
    - ("f64", (hi, lo, mask_hi, mask_lo))    spans fit 64 bits together
    - ("gen", None)                          fall back to the general split
    """
    ts_min = np.int64(ts64.min())
    seq_max = np.uint64(seq64.max())
    # Python-int span: int64-wide ranges must not wrap (see pack_ranked_key).
    ts_bits = (int(ts64.max()) - int(ts_min)).bit_length()
    seq_bits = int(seq_max - np.uint64(seq64.min())).bit_length()
    if ts_bits + seq_bits <= 32:
        rest = (
            (ts64 - ts_min).astype(np.uint32) << np.uint32(seq_bits)
        ) | (seq_max - seq64).astype(np.uint32)
        mask = np.uint32(0xFFFFFFFF) ^ np.uint32((1 << seq_bits) - 1)
        return "f32", (rest, mask)
    if ts_bits + seq_bits <= 64:
        rest64 = (
            (ts64 - ts_min).astype(np.uint64) << np.uint64(seq_bits)
        ) | (seq_max - seq64)
        hi, lo = split_u64(rest64)
        if seq_bits >= 32:
            mask_lo = np.uint32(0)
            mask_hi = np.uint32(0xFFFFFFFF) ^ np.uint32((1 << (seq_bits - 32)) - 1)
        else:
            mask_lo = np.uint32(0xFFFFFFFF) ^ np.uint32((1 << seq_bits) - 1)
            mask_hi = np.uint32(0xFFFFFFFF)
        return "f64", (hi, lo, mask_hi, mask_lo)
    return "gen", None


class MergeHandle:
    """An in-flight device merge: the sort was dispatched asynchronously
    (JAX async dispatch — the device computes while the host keeps
    running); ``get()`` blocks for the result. Lets a caller pipeline the
    host-side payload gather of chunk i with the device sort of chunk
    i+1."""

    __slots__ = ("_out", "_n", "_key")

    def __init__(self, out, n: int, key: tuple | None) -> None:
        self._out, self._n, self._key = out, n, key

    def get(self) -> tuple[np.ndarray, np.ndarray]:
        perm, keep = jax.device_get(self._out)  # one RTT for both outputs
        if self._key is not None:  # n==0 ran no kernel: nothing compiled
            with _compile_lock:
                _ready.add(self._key)  # direct callers warm it too
        return perm[: self._n], keep[: self._n]


def pack_ranked_key(
    tsid_rank: np.ndarray,
    ts64: np.ndarray,
    seq64: np.ndarray,
    n_ranks: int,
):
    """Pack (tsid-rank, ts, seq desc) into ONE order-preserving u64 per
    row — built ONCE for a whole merge; the chunked pipeline then ships
    8 bytes/row and sorts 2 u32 keys. None when the measured bit widths
    exceed 63 (the all-ones pad value must stay strictly greater).
    Returns (composite u64 array, dedup mask_hi, mask_lo) — the masks
    zero the seq bits so the dedup compare sees (rank, ts) only."""
    ts_min = np.int64(ts64.min())
    seq_max = np.uint64(seq64.max())
    # Python-int arithmetic: an int64 span >= 2^63 must NOT wrap (a
    # wrapped width would pick a too-narrow kernel and mis-merge).
    ts_bits = (int(ts64.max()) - int(ts_min)).bit_length()
    seq_bits = int(seq_max - np.uint64(seq64.min())).bit_length()
    rank_bits = max(1, int(n_ranks - 1).bit_length())
    if rank_bits + ts_bits + seq_bits > 63:
        return None
    comp = (
        (tsid_rank.astype(np.uint64) << np.uint64(ts_bits + seq_bits))
        | ((ts64 - ts_min).astype(np.uint64) << np.uint64(seq_bits))
        | (seq_max - seq64)
    )
    if seq_bits >= 32:
        mask_lo = np.uint32(0)
        mask_hi = np.uint32(0xFFFFFFFF) ^ np.uint32((1 << (seq_bits - 32)) - 1)
    else:
        mask_lo = np.uint32(0xFFFFFFFF) ^ np.uint32((1 << seq_bits) - 1)
        mask_hi = np.uint32(0xFFFFFFFF)
    return comp, mask_hi, mask_lo


def merge_dedup_dispatch_packed(
    comp: np.ndarray,
    mask_hi: np.uint32,
    mask_lo: np.uint32,
    dedup: bool = True,
    require_ready: bool = False,
) -> MergeHandle | None:
    """Dispatch the 2-key unstable kernel on a pre-packed composite (see
    pack_ranked_key). Caller guarantees composite uniqueness. With
    ``require_ready``, None when the kernel isn't compiled yet (a
    background compile is kicked off)."""
    n = len(comp)
    if require_ready and not _ready_or_start_compile(
        ("rk", shape_bucket(n), dedup)
    ):
        return None
    hi, lo = split_u64(comp)
    args = [
        pad_to_bucket(hi, n, fill=_U32_MAX),
        pad_to_bucket(lo, n, fill=_U32_MAX),
    ]
    out = _ranked_kernel(
        *(jnp.asarray(a) for a in args),
        jnp.uint32(mask_hi), jnp.uint32(mask_lo), jnp.int32(n),
        dedup=dedup,
    )
    return MergeHandle(out, n, ("rk", shape_bucket(n), dedup))


def merge_dedup_dispatch(
    tsid: np.ndarray,
    ts: np.ndarray,
    seq: np.ndarray,
    dedup: bool = True,
    tsid_rank: np.ndarray | None = None,
    n_ranks: int = 0,
    unique: bool = False,
    require_ready: bool = False,
) -> MergeHandle | None:
    """Asynchronously dispatch the merge-sort kernel; see
    merge_dedup_permutation for semantics. The returned handle's ``get()``
    yields ``(perm, keep)``.

    ``tsid_rank``/``n_ranks``: dense ranks of each row's tsid in the
    merge's sorted tsid universe (compaction builds them for free from
    its sorted input runs). ``unique=True`` asserts no two rows share
    (tsid, ts, seq) — true for deduped runs with distinct per-file
    sequences. Together they unlock the 2-key unstable packed kernel when
    the measured bit widths fit 63 bits.

    ``require_ready``: None instead of a compile stall when the kernel
    the DATA routes to (which may be wider than the one
    merge_dedup_ready pre-warms) isn't compiled — a background compile
    starts and the caller takes its host path."""
    n = len(tsid)
    if n == 0:
        return MergeHandle(
            (np.empty(0, dtype=np.int32), np.empty(0, dtype=np.bool_)), 0,
            None,
        )

    ts64 = ts.astype(np.int64, copy=False)
    seq64 = seq.astype(np.uint64, copy=False)

    if tsid_rank is not None and unique:
        packed_key = pack_ranked_key(tsid_rank, ts64, seq64, n_ranks)
        if packed_key is not None:
            comp, mask_hi, mask_lo = packed_key
            return merge_dedup_dispatch_packed(
                comp, mask_hi, mask_lo, dedup, require_ready=require_ready
            )

    kind, packed = _pack_rest(ts64, seq64)
    if require_ready and not _ready_or_start_compile(
        (kind, shape_bucket(n), dedup)
    ):
        return None

    if kind == "gen":
        tsid_hi, tsid_lo = split_u64(tsid)
        ts_hi, ts_lo = split_i64_sortable(ts64)
        negseq = ~seq64
        negseq_hi, negseq_lo = split_u64(negseq)
        is_pad = pad_to_bucket(np.zeros(n, dtype=np.uint32), n, fill=1)
        args = [
            is_pad,
            pad_to_bucket(tsid_hi, n),
            pad_to_bucket(tsid_lo, n),
            pad_to_bucket(ts_hi, n),
            pad_to_bucket(ts_lo, n),
            pad_to_bucket(negseq_hi, n),
            pad_to_bucket(negseq_lo, n),
        ]
        out = _general_kernel(*(jnp.asarray(a) for a in args), dedup=dedup)
    else:
        # Reverse BEFORE splitting/padding: stable sort + reversed input
        # = newest input row first among exact-duplicate (key, seq) rows.
        rev = slice(None, None, -1)
        tsid_hi, tsid_lo = split_u64(tsid[rev])
        if kind == "f32":
            rest, mask = packed
            args = [
                pad_to_bucket(tsid_hi, n, fill=_U32_MAX),
                pad_to_bucket(tsid_lo, n, fill=_U32_MAX),
                pad_to_bucket(rest[rev], n, fill=_U32_MAX),
            ]
            out = _fused32_kernel(
                *(jnp.asarray(a) for a in args),
                jnp.uint32(mask), jnp.int32(n), dedup=dedup,
            )
        else:
            hi, lo, mask_hi, mask_lo = packed
            args = [
                pad_to_bucket(tsid_hi, n, fill=_U32_MAX),
                pad_to_bucket(tsid_lo, n, fill=_U32_MAX),
                pad_to_bucket(hi[rev], n, fill=_U32_MAX),
                pad_to_bucket(lo[rev], n, fill=_U32_MAX),
            ]
            out = _fused64_kernel(
                *(jnp.asarray(a) for a in args),
                jnp.uint32(mask_hi), jnp.uint32(mask_lo), jnp.int32(n),
                dedup=dedup,
            )

    return MergeHandle(out, n, (kind, shape_bucket(n), dedup))


def merge_dedup_permutation(
    tsid: np.ndarray,
    ts: np.ndarray,
    seq: np.ndarray,
    dedup: bool = True,
    tsid_rank: np.ndarray | None = None,
    n_ranks: int = 0,
    unique: bool = False,
    require_ready: bool = False,
) -> tuple[np.ndarray, np.ndarray] | None:
    """Merge-sort order + survivor mask for concatenated sorted runs.

    Returns ``(perm, keep)`` of length == len(input): ``perm`` is the row
    permutation sorting by (tsid, ts, seq desc); ``keep[i]`` says whether
    sorted position i survives dedup (first — i.e. newest-sequence — row of
    each (tsid, ts) key). Apply as ``rows.take(perm[keep])``. With
    ``require_ready``, None when the routed kernel isn't compiled yet
    (background compile started; caller takes its host path).

    The device does all comparison work; callers gather payload columns
    host-side (string columns can't live on device anyway).
    """
    h = merge_dedup_dispatch(
        tsid, ts, seq, dedup=dedup,
        tsid_rank=tsid_rank, n_ranks=n_ranks, unique=unique,
        require_ready=require_ready,
    )
    return None if h is None else h.get()
