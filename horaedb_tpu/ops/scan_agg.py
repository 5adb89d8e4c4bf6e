"""The fused scan/filter/time-bucket/group-by/aggregate kernel.

This is the north-star insertion point (BASELINE.json): a plan whose leaves
are SST scans with filter + group-by-time + aggregate on top compiles into
ONE XLA program. The reference executes the same shape of work as a
DataFusion operator pipeline (filter -> repartition -> partial agg -> final
agg, survey §3.2); here XLA fuses mask computation, bucketing, and segment
reductions into a single device launch over dense column buffers.

Layout contract (prepared by ops.encoding on host):

- ``group_codes`` int32[N]: dense group index per row;
- ``bucket_ids``  int32[N]: time bucket per row;
- ``mask``        bool[N]:  validity & tag-filter & pad mask;
- ``values``      f32[F, N]: field columns (agg fields first, then any
                  fields referenced only by numeric filters);
- numeric filters evaluate ON DEVICE: ops are static (part of the jit
  key), literals are traced scalars (no recompile when the constant
  changes).

Aggregation state is the classic monoid (count, sum, min, max): partials
from different batches/SSTs/devices combine associatively — the same
combine drives multi-batch scans, distributed partial aggregation over a
mesh (psum), and final agg after dedup.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.device import note_folded_chunks
from .encoding import (
    PaddedBatch,
    decode_layouts as _decode_layouts,
    lookup_series,
    next_pow2,
)

AGG_OPS = ("count", "sum", "min", "max", "avg")

# Numeric filter ops, by static code (part of the jit cache key).
_FILTER_OPS = {"=": 0, "!=": 1, "<": 2, "<=": 3, ">": 4, ">=": 5}

# The segment-reduction kernels, timed on the v5e (PERF.md §5-6): the
# scatter sorts (segment, iota) and costs 7-11 ns a row whatever is moved
# (two scatter-adds are 146 of 158.5 ms at 2^23 rows x 16,384 segments, 651
# of 721.7 ms at 2^25 x 65,536); the one-hot matmul rides the MXU with
# O(N * n_seg) work (2.494 ms against the scatter's 2.667 on a gathered
# 64-segment subset, 480.4 against 158.5 at 16,384 segments). ``single``
# is the plain reduction of one segment (1.868 ms at 2^23 rows). Which one
# serves a query is decided in query/kernel_choice.py and nowhere else: the
# spec's ``segment_impl`` carries the choice into the jit cache key, and
# every entry below takes the name as given.
SEGMENT_KERNELS = ("mxu", "scatter")
# f32 one-hot counts are exact up to 2^24 rows per segment; beyond that the
# count matvec runs in row chunks with int32 accumulation between chunks.
_COUNT_CHUNK = 1 << 24
# Bytes of (n_seg, rows) segment-match mask one MXU-impl min/max step may
# hold in HBM (1 B/cell). Unchunked, 2^21 rows x 8192 segments asked the
# v5e compiler for 16 GB and was refused.
_MINMAX_MASK_BYTES = 1 << 28
# Bytes of the row-major (rows, fields) update tile one step of the scatter
# impl may hold in HBM (``scatter_chunk_rows``).
_SCATTER_TILE_BYTES = 1 << 25
# A scatter step of C rows folds each run of equal segment ids into one update
# row when it holds at most C / _FOLD_SHARE runs (``fold_cap``), and scatters
# its rows as they are otherwise. The scatter costs 7-11 ns an update row on
# the v5e whatever the row holds (PERF.md §5); resident rows lie in series and
# time order, so a host x hour group is a run of 360 rows (~182 runs in a
# 2^16-row step) where short runs (6 rows: ~11,000) fold nothing. The fold's
# own time grows with the cap (a run slot reads a lane row per field at each
# of its ends, ``_run_totals``): at 2^25 rows 29 ms at C/256, 104 at C/64.
_FOLD_SHARE = 256
# Rows of one block of the fold's run reduction: one lane row of the chip.
_FOLD_BLOCK = 128
# Rows one iteration of the scatter impl's loop takes at most (``fold_group``):
# when all its steps fold, one fold and one scatter take all their runs. A
# step at a time the fold is ~40 device operations a step, 20,000 a query at
# 2^25 rows.
_FOLD_GROUP_ROWS = 1 << 21
# The mapped axis of the cohort program (``_cohort_body``): its members agree
# on which steps fold, so each choice runs one branch and not both.
_COHORT_AXIS = "cohort"


def concrete_impl(segment_impl: str) -> str:
    """``segment_impl`` if it names a kernel, else ValueError: the kernels
    run the chooser's answer (``query/kernel_choice.choose``) and derive
    none themselves."""
    if segment_impl != "single" and segment_impl not in SEGMENT_KERNELS:
        raise ValueError(
            f"segment_impl {segment_impl!r}: a kernel takes one of "
            f"{('single',) + SEGMENT_KERNELS}, as query/kernel_choice names it"
        )
    return segment_impl


@dataclass(frozen=True)
class ScanAggSpec:
    """Static shape/op configuration — the jit cache key."""

    n_groups: int  # padded
    n_buckets: int  # padded
    n_agg_fields: int
    # ((value_row_index, op_str), ...) evaluated on device against literals
    numeric_filters: tuple[tuple[int, str], ...] = ()
    # False when no min/max aggregate is requested: the kernel skips the
    # min/max reductions entirely and returns zeros in their slots.
    need_minmax: bool = True
    # Segment-reduction impl for this dispatch: "auto" until
    # ``query/kernel_choice.choose`` names "single" or one of
    # SEGMENT_KERNELS — no kernel runs an unchosen spec. Static jit arg:
    # the chosen kernel IS part of the compile cache key, on the direct,
    # cached, and shard_map dist paths alike.
    segment_impl: str = "auto"
    # Compressed-layout descriptors (ops.encoding, ISSUE 19). Static and
    # hashable: flipping a column's layout re-keys the trace, exactly like
    # a segment-impl change. () / ("raw",) are the legacy dense layouts.
    value_layouts: tuple = ()  # per-field, e.g. (("raw",), ("dict", 7, True))
    ts_layout: tuple = ("raw",)
    series_layout: tuple = ("raw",)

    def padded(self) -> "ScanAggSpec":
        # Ungrouped specs (n_groups == 1) skip group padding entirely: the
        # group count is not query-dependent for them (one stable compile),
        # and padding to 8 would multiply segment work for nothing. When
        # additionally n_buckets == 1 (global aggregate), n_seg stays 1
        # and the pure-reduction kernel applies; bucketed ungrouped
        # queries still pad n_buckets below.
        return ScanAggSpec(
            n_groups=next_pow2(self.n_groups, floor=8) if self.n_groups > 1 else 1,
            n_buckets=next_pow2(self.n_buckets, floor=1),
            n_agg_fields=self.n_agg_fields,
            numeric_filters=self.numeric_filters,
            need_minmax=self.need_minmax,
            segment_impl=self.segment_impl,
            value_layouts=self.value_layouts,
            ts_layout=self.ts_layout,
            series_layout=self.series_layout,
        )


def _mxu_counts(seg, m, n_seg: int):
    """Per-segment row counts via one-hot matvec on the MXU.

    ``seg`` must be -1 for masked rows (one_hot maps OOB to a zero row).
    0/1 products are exact in any matmul precision; chunked int32
    accumulation keeps counts exact past 2^24 rows per segment.
    """
    n = seg.shape[0]
    mf = m.astype(jnp.float32)
    if n <= _COUNT_CHUNK:
        oh = jax.nn.one_hot(seg, n_seg, dtype=jnp.float32)
        return (mf @ oh).astype(jnp.int32)
    n_chunks = -(-n // _COUNT_CHUNK)
    pad = n_chunks * _COUNT_CHUNK - n
    seg_c = jnp.pad(seg, (0, pad), constant_values=-1).reshape(n_chunks, _COUNT_CHUNK)
    m_c = jnp.pad(mf, (0, pad)).reshape(n_chunks, _COUNT_CHUNK)

    def step(acc, xs):
        s, mm = xs
        oh = jax.nn.one_hot(s, n_seg, dtype=jnp.float32)
        return acc + (mm @ oh).astype(jnp.int32), None

    counts, _ = jax.lax.scan(step, jnp.zeros((n_seg,), jnp.int32), (seg_c, m_c))
    return counts


def _mxu_segment_agg(seg_raw, m, agg_vals, n_seg: int, need_minmax: bool):
    """(counts, sums, mins, maxs) over flat segment ids, MXU-style.

    sums ride a (F, N) @ (N, n_seg) one-hot matmul at precision=highest
    (f32-faithful; 'default' bf16 inputs cost ~1e-3 relative error);
    min/max are a masked broadcast-reduce over (F, n_seg, N), run in row
    chunks (``_mxu_minmax``) — scatter never appears.
    """
    seg = jnp.where(m, seg_raw, -1)
    counts = _mxu_counts(seg, m, n_seg)
    if agg_vals is None:
        return counts, None, None, None
    mf = m.astype(agg_vals.dtype)
    oh = jax.nn.one_hot(seg, n_seg, dtype=jnp.float32)
    sums = jax.lax.dot_general(
        agg_vals * mf, oh, (((1,), (0,)), ((), ())), precision="highest"
    )  # (F, n_seg)
    if need_minmax:
        mins, maxs = _mxu_minmax(seg, agg_vals, n_seg)
    else:
        mins = maxs = jnp.zeros_like(sums)
    return counts, sums, mins, maxs


def _mxu_minmax(seg, agg_vals, n_seg: int):
    """Per-segment (mins, maxs), each (F, n_seg), by masked
    broadcast-reduce. ``seg`` is -1 for masked rows (matches no id).

    The TPU compiler materializes the (n_seg, rows) match mask — it feeds
    both reduces — so the reduce runs over row chunks that keep the mask
    under ``_MINMAX_MASK_BYTES``; min/max combine exactly across chunks.
    """
    big = jnp.asarray(jnp.inf, dtype=agg_vals.dtype)
    ids = jnp.arange(n_seg, dtype=seg.dtype)

    def reduce(s, v):
        eq = s[None, :] == ids[:, None]  # (n_seg, rows)
        return (
            jnp.min(jnp.where(eq[None], v[:, None, :], big), axis=-1),
            jnp.max(jnp.where(eq[None], v[:, None, :], -big), axis=-1),
        )

    n = seg.shape[0]
    chunk = max(_MINMAX_MASK_BYTES // n_seg, 128)
    if n <= chunk:
        return reduce(seg, agg_vals)
    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n
    seg = jnp.pad(seg, (0, pad), constant_values=-1)
    agg_vals = jnp.pad(agg_vals, ((0, 0), (0, pad)))

    def step(acc, i):
        mn, mx = reduce(
            jax.lax.dynamic_slice_in_dim(seg, i * chunk, chunk),
            jax.lax.dynamic_slice_in_dim(agg_vals, i * chunk, chunk, axis=1),
        )
        return (jnp.minimum(acc[0], mn), jnp.maximum(acc[1], mx)), None

    shape = (agg_vals.shape[0], n_seg)
    (mins, maxs), _ = jax.lax.scan(
        step,
        (jnp.full(shape, big), jnp.full(shape, -big)),
        jnp.arange(n_chunks, dtype=jnp.int32),
    )
    return mins, maxs


def _single_segment_agg(m, agg_vals, need_minmax: bool):
    """n_seg == 1 (global aggregate, no GROUP BY / no time bucket): plain
    masked reductions. Both the scatter path (4 scalarized segment_* ops)
    and the MXU path (a width-1 one-hot matmul) waste passes here; four
    streaming reduces are the bandwidth floor. ~25% faster than scatter
    on XLA-CPU at 2M rows (measured on the high-cpu-all shape)."""
    counts = m.sum(dtype=jnp.int32)[None]
    if agg_vals is None:
        return counts, None, None, None
    mf = m.astype(agg_vals.dtype)
    sums = (agg_vals * mf).sum(axis=1, keepdims=True)
    if need_minmax:
        big = jnp.asarray(jnp.inf, dtype=agg_vals.dtype)
        mins = jnp.where(m, agg_vals, big).min(axis=1, keepdims=True)
        maxs = jnp.where(m, agg_vals, -big).max(axis=1, keepdims=True)
    else:
        mins = maxs = jnp.zeros_like(sums)
    return counts, sums, mins, maxs


def _tile_row_bytes(n_fields: int) -> int:
    """Bytes a row of a ``(rows, fields)`` f32 tile takes on the TPU, which
    pads the minor axis to 128 lanes: 512 B for any ``n_fields`` <= 128."""
    return 4 * 128 * -(-max(n_fields, 1) // 128)


def scatter_chunk_rows(n_fields: int) -> int:
    """Rows one step of the scatter impl takes: the largest power of two whose
    ``(rows, fields)`` update tile stays under ``_SCATTER_TILE_BYTES``."""
    rows = max(_SCATTER_TILE_BYTES // _tile_row_bytes(n_fields), 128)
    return 1 << (rows.bit_length() - 1)


def segment_row_chunks(impl: str, n_rows: int, n_seg: int, n_fields: int,
                       need_minmax: bool) -> int:
    """How many row chunks ``impl``'s segment reduction runs ``n_rows`` in
    (1: in one piece) — the host-side mirror of the in-trace cuts, for the
    ``dispatch`` span's ``chunks`` attribute."""
    if impl == "scatter":
        return max(1, -(-n_rows // scatter_chunk_rows(n_fields)))
    if impl == "mxu" and need_minmax and n_fields:
        return max(1, -(-n_rows // max(_MINMAX_MASK_BYTES // n_seg, 128)))
    return 1


def segment_temp_bytes(impl: str, n_rows: int, n_seg: int, n_fields: int,
                       need_minmax: bool) -> int:
    """HBM ``impl``'s segment reduction needs beside the resident columns, from
    above: what the policy holds against the device's free memory before it
    offers the impl (``query/kernel_choice.candidate_kernels``). Every impl
    keeps a handful of row-length vectors (segment ids, mask, a scatter's
    sort keys and permutation); the scatter holds one update tile and one
    accumulator (in and out) per reduction, each padded to 128 lanes, and a
    group of steps' fold (``fold_group``) per step its run-end flags as
    int32 blocks, its values cut into blocks (F padded to 8 sublanes) and,
    per reduction, its run tile (``fold_cap`` rows of 512 B) and the two
    lane rows it reads a run; the MXU impl its min/max match mask.
    ``tests/test_tpu_compile.py`` holds it against the v5e compiler's own
    accounting at 2^21 and 2^25 rows."""
    row_vectors = 8 * 4 * n_rows
    if impl == "single":
        return row_vectors
    if impl == "mxu":
        return row_vectors + (
            2 * _MINMAX_MASK_BYTES if need_minmax and n_fields else 0
        )
    reductions = (3 if need_minmax else 1) if n_fields else 0
    row_bytes = _tile_row_bytes(n_fields)
    step = min(n_rows, scatter_chunk_rows(n_fields))
    tile = step * row_bytes
    sublane_bytes = 4 * 8 * -(-max(n_fields, 1) // 8)
    slots = fold_cap(step) + 1
    fold = fold_group(-(-n_rows // step), step) * (
        4 * step + sublane_bytes * step
        + reductions * slots * (row_bytes + 2 * _FOLD_BLOCK * sublane_bytes)
    )
    accumulators = 2 * (n_seg + 1) * (4 + reductions * row_bytes)
    return row_vectors + reductions * tile + fold + accumulators


def fold_cap(rows: int) -> int:
    """Runs a scatter step of ``rows`` rows folds at most (``_FOLD_SHARE``)."""
    return max(rows // _FOLD_SHARE, 1)


def fold_group(n_chunks: int, chunk: int) -> int:
    """Steps of ``chunk`` rows the scatter impl takes at once: ``n_chunks``
    cut evenly into the fewest groups of at most ``_FOLD_GROUP_ROWS`` rows
    (the loop pads the last group with steps of dump rows)."""
    groups = -(-n_chunks // max(_FOLD_GROUP_ROWS // chunk, 1))
    return -(-n_chunks // groups)


def _scatter_segment_agg(seg_raw, m, agg_vals, n_seg: int, need_minmax: bool,
                         cohort_axis: str | None = None):
    """(counts, sums, mins, maxs, folded) via scatter ops (CPU/GPU, or large
    segment counts where O(N*n_seg) matmul work loses to O(N)); ``folded`` is
    the int32 number of steps that scattered one row per run (``_fits``).

    The scatters take their updates row-major, ``(rows, F)``, so the value
    columns transpose first, and that tile is what the program holds beside
    the resident columns: 4.3 GB at 2^23 rows, refused by the v5e's compiler
    at 2^25 (17.2 GB). Above ``scatter_chunk_rows`` rows the scatters
    therefore run over row chunks in a ``lax.scan`` INTO carried
    accumulators, ``fold_group`` steps an iteration: a run cut by a chunk
    boundary is two updates of its segment, so counts are exact and sums
    round as f32 sums do. Under the cohort's ``vmap``, ``cohort_axis`` names
    its axis: a step folds where it folds for every member."""
    seg = jnp.where(m, seg_raw, n_seg)  # masked rows land in a dump slot
    n = seg.shape[0]
    n_fields = 0 if agg_vals is None else agg_vals.shape[0]
    acc = _scatter_zero(n_seg, n_fields, need_minmax,
                        None if agg_vals is None else agg_vals.dtype)
    folded = jnp.int32(0)
    if n:  # a selective program's pick may be empty
        chunk = min(n, scatter_chunk_rows(n_fields))
        n_chunks = -(-n // chunk)
        group = fold_group(n_chunks, chunk)
        n_iter = -(-n_chunks // group)
        rows = group * chunk
        pad = n_iter * rows - n
        if pad:
            seg = jnp.pad(seg, (0, pad), constant_values=n_seg)
            m = jnp.pad(m, (0, pad))
            if agg_vals is not None:
                agg_vals = jnp.pad(agg_vals, ((0, 0), (0, pad)))

        def step(carry, i):
            acc, folded = carry
            with jax.named_scope("slice"):
                s = jax.lax.dynamic_slice_in_dim(seg, i * rows, rows)
                mm = jax.lax.dynamic_slice_in_dim(m, i * rows, rows)
                v = None if agg_vals is None else jax.lax.dynamic_slice_in_dim(
                    agg_vals, i * rows, rows, axis=1
                ).reshape(n_fields, group, chunk)
                s, mm = s.reshape(group, chunk), mm.reshape(group, chunk)
            with jax.named_scope("runs"):
                fits = jax.vmap(_fits)(s)
                if cohort_axis is not None:
                    fits = jax.lax.pmin(fits.astype(jnp.int32), cohort_axis) > 0
            acc = _scatter_group(acc, s, mm, v, fits, n_seg, need_minmax)
            real = i * group + jax.lax.iota(jnp.int32, group) < n_chunks
            return (acc, folded + (fits & real).sum(dtype=jnp.int32)), None

        if n_iter == 1:
            (acc, folded), _ = step((acc, folded), 0)
        else:
            (acc, folded), _ = jax.lax.scan(
                step, (acc, folded), jnp.arange(n_iter, dtype=jnp.int32)
            )
    counts = acc[0][:n_seg]
    if agg_vals is None:
        return counts, None, None, None, folded
    sums = acc[1][:n_seg].T
    if need_minmax:
        return counts, sums, acc[2][:n_seg].T, acc[3][:n_seg].T, folded
    return counts, sums, jnp.zeros_like(sums), jnp.zeros_like(sums), folded


def _scatter_zero(n_seg: int, n_fields: int, need_minmax: bool, dtype):
    """The scatter impl's empty accumulators, dump slot included: counts
    ``(n_seg + 1,)`` and, per wanted reduction, ``(n_seg + 1, F)``."""
    acc = [jnp.zeros((n_seg + 1,), jnp.int32)]
    if n_fields:
        acc.append(jnp.zeros((n_seg + 1, n_fields), dtype))
        if need_minmax:
            big = jnp.asarray(jnp.inf, dtype=dtype)
            acc.append(jnp.full((n_seg + 1, n_fields), big))
            acc.append(jnp.full((n_seg + 1, n_fields), -big))
    return tuple(acc)


def _scatter_rows(acc, seg, m, agg_vals, need_minmax: bool):
    """Fold one run of rows into the accumulators of ``_scatter_zero``."""
    with jax.named_scope("counts"):
        out = [acc[0].at[seg].add(m.astype(jnp.int32))]
    if agg_vals is None:
        return tuple(out)
    with jax.named_scope("sums"):
        out.append(acc[1].at[seg].add((agg_vals * m.astype(agg_vals.dtype)).T))
    if need_minmax:
        big = jnp.asarray(jnp.inf, dtype=agg_vals.dtype)
        with jax.named_scope("mins"):
            out.append(acc[2].at[seg].min(jnp.where(m, agg_vals, big).T))
        with jax.named_scope("maxs"):
            out.append(acc[3].at[seg].max(jnp.where(m, agg_vals, -big).T))
    return tuple(out)


def _scatter_group(acc, seg, m, agg_vals, fits, n_seg: int, need_minmax: bool):
    """Steps ``seg``, ``m`` (steps, rows) and values (F, steps, rows) into the
    accumulators, ``fits`` saying which steps fold. When every one does, one
    fold and one scatter take all their runs (``_fold_rows``); else each step
    folds or scatters its rows (``_scatter_rows``) on its own."""
    def each(acc):
        def one(acc, j):
            s, mm = seg[j], m[j]
            v = None if agg_vals is None else agg_vals[:, j]
            return jax.lax.cond(
                fits[j],
                lambda acc: _fold_rows(
                    acc, s[None], None if v is None else v[:, None], n_seg,
                    need_minmax,
                ),
                lambda acc: _scatter_rows(acc, s, mm, v, need_minmax),
                acc,
            ), None

        if seg.shape[0] == 1:
            v = None if agg_vals is None else agg_vals[:, 0]
            return _scatter_rows(acc, seg[0], m[0], v, need_minmax)
        return jax.lax.scan(
            one, acc, jnp.arange(seg.shape[0], dtype=jnp.int32)
        )[0]

    return jax.lax.cond(
        fits.all(),
        lambda acc: _fold_rows(acc, seg, agg_vals, n_seg, need_minmax),
        each, acc,
    )


def _fits(seg):
    """Whether a step's rows hold at most ``fold_cap`` runs."""
    changes = (seg[1:] != seg[:-1]).sum(dtype=jnp.int32)
    return changes < fold_cap(seg.shape[0])  # runs = changes + 1


def _fold_rows(acc, seg, agg_vals, n_seg: int, need_minmax: bool):
    """Scatter one update row per run of steps of ``<= fold_cap`` runs each,
    ``seg`` (steps, rows), values (F, steps, rows): one scatter for all.

    The rows are cut into blocks of ``_FOLD_BLOCK``; a step that is not a
    whole number of blocks is padded with dump rows, one run more. A run's
    count is its length (every row of a run into a live segment is
    unmasked); its sum, min and max come from ``_run_totals``. Slots past a
    step's runs hold its last row alone, into the dump slot."""
    n = seg.shape[1]
    pad = -n % _FOLD_BLOCK
    slots = fold_cap(n) + (pad > 0)
    if pad:
        seg = jnp.pad(seg, ((0, 0), (0, pad)), constant_values=n_seg)
        if agg_vals is not None:
            agg_vals = jnp.pad(agg_vals, ((0, 0), (0, 0), (0, pad)))
    n += pad

    def runs_of(seg):
        end = jnp.concatenate([seg[1:] != seg[:-1], jnp.ones((1,), bool)])
        last = _run_ends(end, slots)
        live = last < n
        last = jnp.minimum(last, n - 1)
        first = jnp.minimum(
            jnp.concatenate([jnp.zeros((1,), jnp.int32), last[:-1] + 1]), last
        )
        return first, last, jnp.where(live, seg[last], n_seg), jnp.where(
            live, last - first + 1, 0
        )

    with jax.named_scope("fold_ends"):
        first, last, ids, counts = jax.vmap(runs_of)(seg)
        ids = ids.reshape(-1)
    with jax.named_scope("counts"):
        out = [acc[0].at[ids].add(counts.reshape(-1))]
    if agg_vals is None:
        return tuple(out)
    with jax.named_scope("fold_totals"):
        tiles = jax.vmap(
            lambda v, f, l: _run_totals(v, f, l, need_minmax), in_axes=(1, 0, 0)
        )(agg_vals, first, last)
        tiles = [t.reshape(-1, t.shape[-1]) for t in tiles]
    with jax.named_scope("sums"):
        out.append(acc[1].at[ids].add(tiles[0]))
    if need_minmax:
        with jax.named_scope("mins"):
            out.append(acc[2].at[ids].min(tiles[1]))
        with jax.named_scope("maxs"):
            out.append(acc[3].at[ids].max(tiles[2]))
    return tuple(out)


def _run_ends(end, slots: int):
    """The last row of each run, in order, ``end`` marking them; past the
    runs, the row count. No sort and no per-row gather or scatter (a v5e
    runs either at 7-11 ns a row): a slot finds its block by the blocks'
    counts of run ends, then its row by the running count of ends along
    that block's one lane row."""
    n = end.shape[0]
    n_blocks = n // _FOLD_BLOCK
    blocks = end.reshape(n_blocks, _FOLD_BLOCK).astype(jnp.int32)
    upto = jnp.cumsum(blocks.sum(axis=1))  # ends in blocks 0..b
    k = jax.lax.iota(jnp.int32, slots)
    before = upto[None, :] <= k[:, None]  # (slots, blocks): wholly before end k
    blk = before.sum(axis=1, dtype=jnp.int32)
    rank = k - jnp.max(jnp.where(before, upto[None, :], 0), axis=1)
    row = jnp.cumsum(jnp.take(blocks, jnp.minimum(blk, n_blocks - 1), axis=0), axis=1)
    pos = (row <= rank[:, None]).sum(axis=1, dtype=jnp.int32)
    return jnp.where(blk < n_blocks, blk * _FOLD_BLOCK + pos, n)


def _run_totals(vals, first, last, need_minmax: bool):
    """Each run's sum (and min, max) of ``vals`` (F, rows), run k being rows
    ``first[k]..last[k]``, as ``(slots, F)`` tiles. The blocks wholly inside
    a run count by their block totals; its first and last block's pieces by
    a masked reduction over those two lane rows. Sums are f32 added in a
    fixed order (piece, blocks, piece): no difference of prefix sums."""
    n_fields, n = vals.shape
    x = vals.reshape(n_fields, n // _FOLD_BLOCK, _FOLD_BLOCK)
    b_first, b_last = first // _FOLD_BLOCK, last // _FOLD_BLOCK
    lane = jax.lax.iota(jnp.int32, _FOLD_BLOCK)[None, :]
    one = (b_first == b_last)[:, None]
    upto_last = lane <= (last % _FOLD_BLOCK)[:, None]
    head = (lane >= (first % _FOLD_BLOCK)[:, None]) & (~one | upto_last)
    tail = ~one & upto_last
    blk = jax.lax.iota(jnp.int32, x.shape[1])[None, :]
    inner = (blk > b_first[:, None]) & (blk < b_last[:, None])  # (slots, blocks)
    head_rows = jnp.take(x, b_first, axis=1)  # (F, slots, block)
    tail_rows = jnp.take(x, b_last, axis=1)
    big = jnp.asarray(jnp.inf, vals.dtype)
    reductions = [(jnp.sum, jnp.add, 0)]
    if need_minmax:
        reductions += [(jnp.min, jnp.minimum, big), (jnp.max, jnp.maximum, -big)]
    tiles = []
    for reduce, combine, ident in reductions:
        h = reduce(jnp.where(head[None], head_rows, ident), axis=2)
        mid = reduce(jnp.where(inner[None], reduce(x, axis=2)[:, None, :], ident), axis=2)
        t = reduce(jnp.where(tail[None], tail_rows, ident), axis=2)
        tiles.append(combine(combine(h, mid), t).T)
    return tiles


def scan_agg_body(
    group_codes,
    bucket_ids,
    mask,
    values,
    literals,
    *,
    n_groups: int,
    n_buckets: int,
    n_agg_fields: int,
    numeric_filters: tuple[tuple[int, int], ...] = (),
    need_minmax: bool = True,
    segment_impl: str,
    cohort_axis: str | None = None,
):
    """Pure kernel body — also the per-shard program inside shard_map
    (parallel/dist_agg.py wraps it with psum/pmin/pmax collectives).
    -> (counts, sums, mins, maxs, folded): ``folded`` is the int32 count of
    scatter steps that scattered one row per run (0 for the other impls)."""
    m = mask
    with jax.named_scope("filter"):
        for i, (field_idx, op_code) in enumerate(numeric_filters):
            v = values[field_idx]
            lit = literals[i]
            if op_code == 0:
                m = m & (v == lit)
            elif op_code == 1:
                m = m & (v != lit)
            elif op_code == 2:
                m = m & (v < lit)
            elif op_code == 3:
                m = m & (v <= lit)
            elif op_code == 4:
                m = m & (v > lit)
            else:
                m = m & (v >= lit)

        n_seg = n_groups * n_buckets
        seg_raw = group_codes * n_buckets + bucket_ids
    # ``values`` may be a list of per-field rows (the encoded-layout decode
    # produces one array per field): stack only the agg fields — fields
    # referenced solely by filters never materialize a decoded column.
    if isinstance(values, (list, tuple)):
        agg_vals = jnp.stack(values[:n_agg_fields]) if n_agg_fields else None
    else:
        agg_vals = values[:n_agg_fields] if n_agg_fields else None
    folded = jnp.int32(0)
    with jax.named_scope("segment_" + concrete_impl(segment_impl)):
        if segment_impl == "single":
            counts, sums, mins, maxs = _single_segment_agg(m, agg_vals, need_minmax)
        elif segment_impl == "mxu":
            counts, sums, mins, maxs = _mxu_segment_agg(
                seg_raw, m, agg_vals, n_seg, need_minmax
            )
        else:
            counts, sums, mins, maxs, folded = _scatter_segment_agg(
                seg_raw, m, agg_vals, n_seg, need_minmax, cohort_axis
            )

    counts = counts.reshape(n_groups, n_buckets)
    if n_agg_fields:
        shape = (n_agg_fields, n_groups, n_buckets)
        sums = sums.reshape(shape)
        mins = mins.reshape(shape)
        maxs = maxs.reshape(shape)
    else:
        vdtype = (
            jnp.float32 if isinstance(values, (list, tuple)) else values.dtype
        )
        zero = jnp.zeros((0, n_groups, n_buckets), dtype=vdtype)
        sums = mins = maxs = zero
    return counts, sums, mins, maxs, folded


_fused_scan_agg = functools.partial(
    jax.jit,
    static_argnames=(
        "n_groups", "n_buckets", "n_agg_fields", "numeric_filters",
        "need_minmax", "segment_impl",
    ),
)(scan_agg_body)


def cached_scan_agg_body(
    series_codes,  # int32[N] (padded rows carry code == n_series)
    ts_rel,  # int32[N], ms relative to the cache's min timestamp
    values,  # f32[F, N] device-resident value columns
    group_of_series,  # int32[S+1]; last entry is the pad series' dump group
    allowed_series,  # bool[S+1];  last entry False (pad rows masked out)
    literals,  # f32[n_filters]
    lo_rel,  # int32 scalar: inclusive range start (relative)
    hi_rel,  # int32 scalar: exclusive range end (relative)
    t0_rel,  # int32 scalar: bucket origin (relative, <= lo_rel)
    bucket_ms,  # int32 scalar: bucket width (1 when not bucketing)
    *,
    n_groups: int,
    n_buckets: int,
    n_agg_fields: int,
    numeric_filters: tuple[tuple[int, int], ...],
    need_minmax: bool = True,
    segment_impl: str,
    value_layouts: tuple = (),
    ts_layout: tuple = ("raw",),
    series_layout: tuple = ("raw",),
    cohort_axis: str | None = None,
):
    """The steady-state serving kernel over HBM-resident columns.

    Everything per-query is SMALL: the series->group map, the series
    allow-list (tag filters evaluated per series on host), scalar time
    bounds, and filter literals. The big arrays (series codes, relative
    timestamps, value columns) stay on device across queries — uploads are
    O(series + scalars), not O(rows).

    Compressed layouts (ISSUE 19): when the layout descriptors say so,
    ``series_codes``/``ts_rel`` arrive as encoded part tuples and
    ``values`` as a tuple of per-field part tuples. This body is always a
    scan of ALL its rows (the ``_sel`` programs gather and decode their
    picked rows first and arrive here raw), so the decode below reads each
    packed stream by its static structure — transposes and constant shifts —
    and the per-series tables through the FOR blocks of the series codes
    (``lookup_series``): no gather of N rows, which a v5e runs at 7-9 ns a
    row whatever is gathered (four of them were 271 of this program's 272
    ms at 2^23 rows; PERF.md, PR 25). HBM traffic is the encoded bytes, and
    filter-only dict fields compare raw codes against host-pre-translated
    literals without ever touching the dictionary.

    Pure body: also the per-shard program when the cache is sharded over a
    mesh (parallel/dist_agg.make_cached_dist_scan_agg wraps it with
    psum/pmin/pmax collectives). That path keeps its streams raw; its
    series codes come as ``("blocked", w)`` where every 128-row block of a
    shard's valid rows spans under ``2**w`` series, and the tables are then
    read through the blocks as here, each block's base and offsets computed
    from the raw codes in the program (``encoding.block_series``); else
    per row.
    """
    series, ts_rel, values = _decode_layouts(
        series_codes, ts_rel, values, series_layout, ts_layout, value_layouts,
        blocked_series=True,
    )
    with jax.named_scope("filter"):
        mask = lookup_series(allowed_series, series)
        mask = mask & (ts_rel >= lo_rel) & (ts_rel < hi_rel)
        bucket = jnp.clip((ts_rel - t0_rel) // bucket_ms, 0, n_buckets - 1).astype(jnp.int32)
        group_codes = lookup_series(group_of_series, series)
    if not isinstance(values, (list, tuple)):
        # bf16-resident value columns (HORAEDB_CACHE_DTYPE) upcast here:
        # accumulation always runs in f32 (no-op when already f32)
        values = values.astype(jnp.float32)
    return scan_agg_body(
        group_codes,
        bucket,
        mask,
        values,
        literals,
        n_groups=n_groups,
        n_buckets=n_buckets,
        n_agg_fields=n_agg_fields,
        numeric_filters=numeric_filters,
        need_minmax=need_minmax,
        segment_impl=segment_impl,
        cohort_axis=cohort_axis,
    )


# ---- RTT-minimized packed serving path ------------------------------------
#
# Every host->device buffer transfer and every device->host fetch is a
# round trip to the accelerator. The un-packed cached
# kernel ships ~7 small buffers per query (group map, allow list, literals,
# four scalars, optionally a row index) and fetches four result buffers —
# each a potential RTT. The packed variants collapse that to:
#
#   * ONE per-shape "session" upload (group map + allow list, content-hash
#     cached on the entry so repeated dashboard queries skip it entirely),
#   * ONE per-query int32 "dyn" upload (filter literals bitcast to int32,
#     the four time scalars, and — for the selective kernel — the gathered
#     row index), and
#   * ONE packed int32 result fetch (sums/mins/maxs bitcast into the same
#     buffer as the counts).
#
# Steady state = 1 upload + 1 execute + 1 fetch. The reference never needs
# this because DataFusion executes in-process; an attached accelerator makes
# dispatch cost a first-class design constraint (BASELINE.md north star).


def pack_session(group_of_series: np.ndarray, allowed_series: np.ndarray) -> np.ndarray:
    """[group map | allow list] as one int32 buffer (one upload)."""
    return np.concatenate(
        [group_of_series.astype(np.int32), allowed_series.astype(np.int32)]
    )


def pack_dyn(
    filter_literals: Sequence[float],
    lo_rel: int,
    hi_rel: int,
    t0_rel: int,
    bucket_ms: int,
    row_idx: np.ndarray | None = None,
) -> np.ndarray:
    """Per-query dynamic inputs as one int32 buffer (one upload).

    f32 literals travel bitcast (the kernel bitcasts them back); the
    selective kernel's row index rides the same buffer.
    """
    lits = np.asarray(filter_literals, dtype=np.float32).view(np.int32)
    scalars = np.array([lo_rel, hi_rel, t0_rel, bucket_ms], dtype=np.int32)
    if row_idx is None:
        return np.concatenate([lits, scalars])
    return np.concatenate([lits, scalars, row_idx.astype(np.int32, copy=False)])


def _packed_body(
    series_codes,
    ts_rel,
    values,
    session,  # int32[2*(S+1)]: [group map | allow list]
    dyn,  # int32[n_f + 4 (+ M)]: [literals(bitcast) | lo,hi,t0,width | idx]
    *,
    n_groups: int,
    n_buckets: int,
    n_agg_fields: int,
    numeric_filters: tuple[tuple[int, int], ...],
    need_minmax: bool,
    segment_impl: str,
    selective: bool = False,
    value_layouts: tuple = (),
    ts_layout: tuple = ("raw",),
    series_layout: tuple = ("raw",),
    cohort_axis: str | None = None,
):
    s1 = session.shape[0] // 2
    gos = session[:s1]
    allow = session[s1:] != 0
    n_f = len(numeric_filters)
    literals = jax.lax.bitcast_convert_type(dyn[:n_f], jnp.float32)
    lo, hi, t0, width = dyn[n_f], dyn[n_f + 1], dyn[n_f + 2], dyn[n_f + 3]
    if selective:
        # decode-on-gather: only the M shipped row positions are read from
        # the encoded streams; the full columns never decode
        idx = dyn[n_f + 4 :]
        series_codes, ts_rel, values = _decode_layouts(
            series_codes, ts_rel, values, series_layout, ts_layout,
            value_layouts, idx=idx,
        )
        value_layouts, ts_layout, series_layout = (), ("raw",), ("raw",)
    counts, sums, mins, maxs, folded = cached_scan_agg_body(
        series_codes, ts_rel, values, gos, allow, literals, lo, hi, t0, width,
        n_groups=n_groups,
        n_buckets=n_buckets,
        n_agg_fields=n_agg_fields,
        numeric_filters=numeric_filters,
        need_minmax=need_minmax,
        segment_impl=segment_impl,
        value_layouts=value_layouts,
        ts_layout=ts_layout,
        series_layout=series_layout,
        cohort_axis=cohort_axis,
    )
    # One INT32 result buffer: the f32 partials travel bitcast beside the
    # counts, never the counts as f32 — small int bit patterns are f32
    # denormals, and the TPU flushes those to zero when it fuses the
    # concatenate (measured on a v5e: every count came back 0). The folded
    # steps' count rides beside the counts.
    with jax.named_scope("pack"):
        parts = [counts.reshape(-1), folded[None], _f32_bits(sums)]
        if need_minmax:
            parts.extend([_f32_bits(mins), _f32_bits(maxs)])
        return jnp.concatenate(parts)


def _f32_bits(x):
    return jax.lax.bitcast_convert_type(x.reshape(-1), jnp.int32)


_PACKED_STATIC = (
    "n_groups", "n_buckets", "n_agg_fields", "numeric_filters",
    "need_minmax", "segment_impl", "selective",
    "value_layouts", "ts_layout", "series_layout",
)
_packed_programs: dict[str, object] = {}


def packed_program_name(segment_impl: str, selective: bool) -> str:
    """What the device trace calls the packed cached scan of one concrete
    segment impl: ``XLA Modules`` reads ``jit_cached_scan_mxu(...)``,
    ``jit_cached_scan_scatter_sel(...)`` (``_sel``: the gathered subset)."""
    return f"cached_scan_{concrete_impl(segment_impl)}" + (
        "_sel" if selective else ""
    )


def _packed_program(name: str):
    """``_packed_body`` jitted under ``name``: one program per name, so the
    trace tells the implementations apart without their fingerprints."""
    program = _packed_programs.get(name)
    if program is None:

        @functools.wraps(_packed_body)
        def body(*args, **kwargs):
            return _packed_body(*args, **kwargs)

        body.__name__ = body.__qualname__ = name
        program = _packed_programs.setdefault(
            name, jax.jit(body, static_argnames=_PACKED_STATIC)
        )
    return program


def _pick_packed(kwargs: dict):
    """The program named for the statics' (segment impl, selective)."""
    return _packed_program(packed_program_name(
        kwargs["segment_impl"], bool(kwargs.get("selective", False))
    ))


def cached_scan_agg_packed(*args, **kwargs):
    """The packed serving kernel's entry: picks the program named for the
    statics' (segment impl, selective) and calls it. ``.lower`` does the
    same for ahead-of-time compiles and ``cost_analysis``."""
    return _pick_packed(kwargs)(*args, **kwargs)


def _lower_packed(*args, **kwargs):
    return _pick_packed(kwargs).lower(*args, **kwargs)


cached_scan_agg_packed.lower = _lower_packed


def _cohort_body(
    series_codes,
    ts_rel,
    values,
    sessions,  # int32[B, 2*(S+1)]: one packed session row per member
    dyns,  # int32[B, n_f + 4]: one packed dyn row per member
    *,
    n_groups: int,
    n_buckets: int,
    n_agg_fields: int,
    numeric_filters: tuple[tuple[int, int], ...],
    need_minmax: bool,
    segment_impl: str,
    value_layouts: tuple = (),
    ts_layout: tuple = ("raw",),
    series_layout: tuple = ("raw",),
):
    """The multi-query fused serving kernel: ``_packed_body`` vmapped
    over the QUERY axis. The big resident arrays (series codes, relative
    timestamps, value columns — raw or encoded part tuples alike)
    broadcast across the batch — HBM is read by one compiled program
    serving B logical queries, instead of B dispatches each paying its
    own device RTT. Selective row-gather is per-query-variable-length and
    therefore excluded: cohort members always run the full-scan kernel.
    The scatter impl's steps fold where they fold for every member
    (``_COHORT_AXIS``)."""
    one = functools.partial(
        _packed_body,
        n_groups=n_groups,
        n_buckets=n_buckets,
        n_agg_fields=n_agg_fields,
        numeric_filters=numeric_filters,
        need_minmax=need_minmax,
        segment_impl=segment_impl,
        selective=False,
        value_layouts=value_layouts,
        ts_layout=ts_layout,
        series_layout=series_layout,
        cohort_axis=_COHORT_AXIS,
    )
    return jax.vmap(
        lambda s, d: one(series_codes, ts_rel, values, s, d),
        axis_name=_COHORT_AXIS,
    )(sessions, dyns)


cached_scan_agg_cohort = functools.partial(
    jax.jit,
    static_argnames=(
        "n_groups", "n_buckets", "n_agg_fields", "numeric_filters",
        "need_minmax", "segment_impl",
        "value_layouts", "ts_layout", "series_layout",
    ),
)(_cohort_body)


def unpack_packed_state(packed, spec: "ScanAggSpec") -> "AggState":
    """ONE blocking device fetch -> writable host AggState.

    The buffer is int32: counts as they are, the f32 partials bitcast;
    the host views their bytes back as f32. Arrays are copies
    (``_fold_delta`` accumulates in place).
    """
    arr = np.asarray(jax.device_get(packed))
    G, B, F = spec.n_groups, spec.n_buckets, spec.n_agg_fields
    gb = G * B
    counts = arr[:gb].reshape(G, B).copy()
    folded = int(arr[gb])
    f32 = arr[gb + 1:].view(np.float32)
    sums = f32[: F * gb].astype(np.float64).reshape(F, G, B)
    if spec.need_minmax and F:
        mins = f32[F * gb : 2 * F * gb].astype(np.float64).reshape(F, G, B)
        maxs = f32[2 * F * gb :].astype(np.float64).reshape(F, G, B)
    else:
        mins = np.zeros((F, G, B))
        maxs = np.zeros((F, G, B))
    note_folded_chunks(folded)
    return AggState(counts=counts, sums=sums, mins=mins, maxs=maxs)


@dataclass
class AggState:
    """Combinable partial aggregates (numpy, on host after device exit)."""

    counts: np.ndarray  # (G, B) int
    sums: np.ndarray  # (F, G, B)
    mins: np.ndarray  # (F, G, B)
    maxs: np.ndarray  # (F, G, B)

    def combine(self, other: "AggState") -> "AggState":
        return AggState(
            counts=self.counts + other.counts,
            sums=self.sums + other.sums,
            mins=np.minimum(self.mins, other.mins),
            maxs=np.maximum(self.maxs, other.maxs),
        )


def scan_aggregate(
    batch: PaddedBatch,
    spec: ScanAggSpec,
    filter_literals: Sequence[float] = (),
) -> AggState:
    """Run the fused kernel on one padded batch; returns host partials.

    ``spec`` should already be ``.padded()`` — callers slice the outputs
    back down to true group/bucket counts after combining partials.
    """
    import time as _time

    from ..obs.device import cost_analysis, timed_dispatch
    from ..utils.querystats import note_kernel_dispatch

    impl = concrete_impl(spec.segment_impl)

    args = (
        jnp.asarray(batch.group_codes),
        jnp.asarray(batch.bucket_ids),
        jnp.asarray(batch.mask),
        jnp.asarray(batch.values),
        coerce_literals(filter_literals),
    )
    kwargs = dict(
        n_groups=spec.n_groups,
        n_buckets=spec.n_buckets,
        n_agg_fields=spec.n_agg_fields,
        numeric_filters=encode_filter_ops(spec.numeric_filters),
        need_minmax=spec.need_minmax,
        segment_impl=impl,
    )
    t0 = _time.perf_counter()
    out = timed_dispatch("fused", lambda: _fused_scan_agg(*args, **kwargs))
    state = state_to_host(*out)
    # Per-query compile accounting: a never-seen static shape's first
    # dispatch pays the XLA compile — its wall time is the honest cost a
    # latency cliff needs attributed (ledger jit_* fields + the device
    # plane's kernel_compile event; cost_fn adds XLA cost_analysis
    # flops/bytes under HORAEDB_DEVICE_COST_ANALYSIS=1).
    note_kernel_dispatch(
        ("fused", batch.values.shape, spec.n_groups, spec.n_buckets,
         spec.n_agg_fields, spec.numeric_filters, spec.need_minmax, impl),
        _time.perf_counter() - t0,
        kind="fused",
        cost_fn=lambda: cost_analysis(_fused_scan_agg, args, kwargs),
    )
    return state


def encode_filter_ops(
    filters: tuple[tuple[int, str], ...]
) -> tuple[tuple[int, int], ...]:
    """Op strings -> the static integer codes scan_agg_body branches on."""
    return tuple((fi, _FILTER_OPS[op]) for fi, op in filters)


def coerce_literals(filter_literals: Sequence[float]):
    return jnp.asarray(np.asarray(filter_literals, dtype=np.float32))


def state_to_host(counts, sums, mins, maxs, folded) -> AggState:
    # One device_get over the pytree = one host<->device round trip; five
    # separate np.asarray fetches cost five round trips to the device.
    counts, sums, mins, maxs, folded = jax.device_get(
        (counts, sums, mins, maxs, folded)
    )
    note_folded_chunks(int(folded))
    return AggState(
        counts=np.asarray(counts),
        sums=np.asarray(sums, dtype=np.float64),
        mins=np.asarray(mins, dtype=np.float64),
        maxs=np.asarray(maxs, dtype=np.float64),
    )
