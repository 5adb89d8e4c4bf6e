"""Host-side encoding that makes columns device-friendly.

XLA wants static shapes and integer keys; time-series data arrives with
ragged row counts, 64-bit epoch timestamps, and string tags. This module is
the boundary where that impedance is resolved, all in vectorized numpy:

- ``shape_bucket``/``pad_to_bucket`` — round row counts up to a small set of
  shape buckets so jit compiles a handful of programs, not one per scan;
- ``encode_group_codes`` — dense int32 group codes from tsid + tag columns
  (per-scan ``np.unique`` at series granularity; strings are only touched
  once per unique series, never per row);
- ``time_buckets`` — int32 bucket ids from int64 epoch-ms timestamps
  (computed host-side so the device never needs 64-bit integers).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..common_types.dict_column import as_values, unique_inverse
from ..common_types.row_group import RowGroup

# Shape buckets: powers of two from 4k up. Anything smaller pads to 4096;
# each jit key above that is exactly 2x the previous, so at most ~17
# compilations cover 4k .. 256M rows.
_MIN_BUCKET = 4096


def next_pow2(n: int, floor: int = 1) -> int:
    b = floor
    while b < n:
        b <<= 1
    return b


def shape_bucket(n: int) -> int:
    return next_pow2(n, _MIN_BUCKET)


def pad_to_bucket(arr: np.ndarray, n_rows: int, fill=0) -> np.ndarray:
    """Pad axis 0 up to ``shape_bucket(n_rows)`` with ``fill``."""
    target = shape_bucket(n_rows)
    if len(arr) == target:
        return arr
    pad_n = target - len(arr)
    pad_block = np.full((pad_n, *arr.shape[1:]), fill, dtype=arr.dtype)
    return np.concatenate([arr, pad_block])


@dataclass(frozen=True)
class GroupEncoding:
    """Per-row dense group codes + the decoded key values per group."""

    codes: np.ndarray  # int32 per row, in [0, num_groups)
    num_groups: int
    # For each output group, the group-by key values (one array per key
    # column, each of length num_groups) — used to label result rows.
    key_values: tuple[np.ndarray, ...]


def encode_group_codes(
    rows: RowGroup,
    group_columns: Sequence[str],
) -> GroupEncoding:
    """Dense int32 group codes for arbitrary group-by key columns.

    Strategy (all C-speed numpy, no Python per-row loops):

    1. `np.unique(tsid, return_inverse)` -> dense series index per row.
       Series count is tiny next to row count in time-series workloads.
    2. The group key of a series is constant unless the key includes
       non-tag columns; when keys are all tags (the common case), compute
       group codes at series granularity and broadcast through the inverse.
    3. Otherwise fall back to row-level np.unique over the key columns.
    """
    schema = rows.schema
    tag_names = set(schema.tag_names)
    n = len(rows)
    if not group_columns:
        return GroupEncoding(np.zeros(n, dtype=np.int32), 1, ())

    all_tags = all(c in tag_names for c in group_columns)
    tsid_idx = schema.tsid_index
    if all_tags and tsid_idx is not None and n > 0:
        tsid = rows.columns[schema.columns[tsid_idx].name]
        uniq_tsid, first_idx, inverse = np.unique(
            tsid, return_index=True, return_inverse=True
        )
        # Key values per unique series (small arrays).
        series_keys = [as_values(rows.columns[c][first_idx]) for c in group_columns]
        series_group, key_values = _codes_from_columns(series_keys)
        codes = series_group[inverse].astype(np.int32)
        return GroupEncoding(codes, len(key_values[0]) if key_values else 1, key_values)

    row_keys = [rows.columns[c] for c in group_columns]
    codes64, key_values = _codes_from_columns(row_keys)
    return GroupEncoding(codes64.astype(np.int32), len(key_values[0]) if key_values else 1, key_values)


def _codes_from_columns(cols: list) -> tuple[np.ndarray, tuple[np.ndarray, ...]]:
    """(codes, unique key values per column) for composite keys."""
    if len(cols) == 1:
        uniq, codes = unique_inverse(cols[0])
        return codes, (uniq,)
    # Composite: successive refinement — code each column, then combine.
    combined = np.zeros(len(cols[0]), dtype=np.int64)
    for c in cols:
        u, inv = unique_inverse(c)
        combined = combined * (len(u) + 1) + inv
    uniq_comb, first_idx, codes = np.unique(
        combined, return_index=True, return_inverse=True
    )
    key_values = tuple(as_values(c[first_idx]) for c in cols)
    return codes, key_values


def time_buckets(
    ts: np.ndarray, t0: int, bucket_ms: int
) -> tuple[np.ndarray, int]:
    """(int32 bucket ids relative to t0, bucket count). Host-side int64
    floor-div so the device kernel never sees 64-bit timestamps.

    Rows before ``t0`` are rejected loudly: negative segment ids would be
    SILENTLY DROPPED by XLA's scatter, corrupting aggregates. Callers must
    time-filter first (merge_read already does) and pass t0 <= min(ts).
    """
    if bucket_ms <= 0:
        raise ValueError(f"bucket_ms must be positive, got {bucket_ms}")
    b = (ts - t0) // bucket_ms
    n = int(b.max()) + 1 if len(b) else 1
    if len(b) and int(b.min()) < 0:
        raise ValueError(
            f"timestamps before bucket origin t0={t0} (min bucket {int(b.min())}); "
            "clip the batch to the query time range first"
        )
    if n > 2**31 - 1:
        raise ValueError(f"bucket count {n} overflows int32; widen bucket_ms")
    return b.astype(np.int32), max(n, 1)


def split_u64(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """uint64 -> (hi uint32, lo uint32) for device sorts without x64."""
    x = x.astype(np.uint64, copy=False)
    return (x >> np.uint64(32)).astype(np.uint32), (x & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def split_i64_sortable(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """int64 -> order-preserving (hi uint32, lo uint32) pair.

    Flipping the sign bit maps int64 order onto uint64 order, so sorting by
    (hi, lo) lexicographically equals sorting by the original int64.
    """
    u = x.astype(np.int64, copy=False).view(np.uint64) ^ np.uint64(1 << 63)
    return split_u64(u)


@dataclass(frozen=True)
class PaddedBatch:
    """A scan batch padded to a shape bucket, ready for the device."""

    n_valid: int
    group_codes: np.ndarray  # int32 (padded)
    bucket_ids: np.ndarray  # int32 (padded)
    mask: np.ndarray  # bool (padded; False in the pad tail)
    values: np.ndarray  # float32, shape (n_fields, padded)

    @property
    def padded_len(self) -> int:
        return len(self.mask)


def build_padded_batch(
    group_codes: np.ndarray,
    bucket_ids: np.ndarray,
    mask: np.ndarray,
    value_cols: Sequence[np.ndarray],
) -> PaddedBatch:
    n = len(group_codes)
    target = shape_bucket(n)
    if value_cols:
        values = np.stack([v.astype(np.float32, copy=False) for v in value_cols])
        values = np.pad(values, ((0, 0), (0, target - n)))
    else:
        values = np.zeros((0, target), dtype=np.float32)
    return PaddedBatch(
        n_valid=n,
        group_codes=pad_to_bucket(group_codes, n),
        bucket_ids=pad_to_bucket(bucket_ids, n),
        mask=pad_to_bucket(mask.astype(np.bool_), n, fill=False),
        values=values,
    )


# ---------------------------------------------------------------------------
# Compressed device layouts (ISSUE 19)
#
# The scan cache stores columns in HBM; capacity, not kernel speed, bounds
# how much of the working set gets device-path serving. These codecs trade
# a few register-level ops per row for 4-8x fewer HBM bytes:
#
# - ``pack_bits`` — a uint32 word stream holding fixed-width codes (1..16
#   bits). One stream, two device unpacks: a full scan reads it by its
#   static structure (``unpack_bits_all``: transposes and constant shifts,
#   no index), decode-on-gather reads the rows an index picks
#   (``unpack_bits``: two gathers). On a v5e a gather costs 7-9 ns per
#   result row whatever it reads, so the gather form is for M << N rows only.
# - ``dict_encode`` — sorted-dictionary encoding for low-cardinality
#   columns: bit-packed codes + a small pow2-padded dictionary. Sorted
#   dictionaries let the executor pre-translate comparison literals into
#   the code domain host-side (filters never decode).
# - ``delta_for_encode`` — block frame-of-reference for sorted-ish int32
#   streams (series codes, per-series relative timestamps): one int32 base
#   per 128-row block + bit-packed offsets.
#
# All codecs are LOSSLESS and verified by bit-exact host roundtrip at
# encode time; callers fall back to the raw layout on any mismatch (the
# -0.0/0.0 collapse under np.unique is caught exactly this way).
#
# Layout descriptors are small hashable tuples that ride jit static args
# (flipping a layout re-keys the trace — the PR-6 lesson):
#
#   value field:  ("raw",) | ("bf16",) | ("dict", width, full_decode)
#   timestamps:   ("raw",) | ("dict", width) | ("delta", width)
#   series codes: ("raw",) | ("delta", width)
# ---------------------------------------------------------------------------

RAW_LAYOUT = ("raw",)
BF16_LAYOUT = ("bf16",)

# Frame-of-reference block size. 128 divides every shape bucket (pow2 >=
# 4096), and series codes — consecutive np.unique inverses, non-decreasing
# — span at most 128 distinct values per block, so offsets always fit 8 bits.
FOR_BLOCK = 128
_FOR_SHIFT = 7

_MAX_CODE_WIDTH = 16


def _bit_width(max_value: int) -> int:
    """Bits needed to store values in [0, max_value] (min 1)."""
    return max(1, int(max_value).bit_length())


def pack_bits(values: np.ndarray, width: int) -> np.ndarray:
    """Pack unsigned ints (< 2**width) into a dense uint32 word stream.

    One trailing safety word is appended so the device unpack may always
    read ``words[wi + 1]`` without bounds checks.
    """
    if not 1 <= width <= _MAX_CODE_WIDTH:
        raise ValueError(f"width must be in [1, {_MAX_CODE_WIDTH}], got {width}")
    v = values.astype(np.uint64, copy=False)
    n = len(v)
    n_words = (n * width + 31) // 32 + 1
    pos = np.arange(n, dtype=np.uint64) * np.uint64(width)
    wi = (pos >> np.uint64(5)).astype(np.int64)
    sh = pos & np.uint64(31)
    shifted = v << sh  # width<=16, sh<=31 -> fits u64
    words = np.zeros(n_words, dtype=np.uint64)
    np.bitwise_or.at(words, wi, shifted & np.uint64(0xFFFFFFFF))
    np.bitwise_or.at(words, wi + 1, shifted >> np.uint64(32))
    return (words & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def unpack_bits_host(words: np.ndarray, width: int, n: int) -> np.ndarray:
    """Host-side mirror of the device unpack (roundtrip verification)."""
    w64 = words.astype(np.uint64)
    pos = np.arange(n, dtype=np.uint64) * np.uint64(width)
    wi = (pos >> np.uint64(5)).astype(np.int64)
    sh = pos & np.uint64(31)
    lo = w64[wi] >> sh
    # shift-by-32 is undefined on fixed-width ints: guard the aligned case
    hi = np.where(sh == np.uint64(0), np.uint64(0), w64[wi + 1] << (np.uint64(32) - sh))
    return ((lo | hi) & np.uint64((1 << width) - 1)).astype(np.uint32)


def unpack_bits(words, width: int, idx):
    """Device random-access unpack: codes at row positions ``idx``.

    ``words`` is the uint32 stream (with safety word); ``idx`` any int32
    index array. Two gathers of ``len(idx)`` rows + shifts: the form for a
    picked subset (the ``_sel`` programs' 4096 rows take 0.03-0.11 ms a
    gather on a v5e). Never for all rows in order — each gather took 60-72
    ms over 2^23 rows there (PERF.md, PR 25); ``unpack_bits_all`` reads
    the same stream with no gather.
    """
    p = idx.astype(jnp.uint32) * jnp.uint32(width)
    wi = (p >> 5).astype(jnp.int32)
    sh = p & jnp.uint32(31)
    lo = words[wi] >> sh
    # (32 - sh) & 31 keeps the shift in range; the sh==0 lane is masked off
    hi = jnp.where(
        sh == 0, jnp.uint32(0), words[wi + 1] << ((jnp.uint32(32) - sh) & jnp.uint32(31))
    )
    return (lo | hi) & jnp.uint32((1 << width) - 1)


def unpack_bits_all(words, width: int, n_rows: int):
    """Device full-scan unpack: codes of rows ``0 .. n_rows - 1``, in order.

    No index and no gather: 32 consecutive codes of ``width`` bits fill
    exactly ``width`` words, so the stream is a ``(n_rows / 32, width)``
    matrix and code ``j`` of a matrix row sits at a STATIC word and shift.
    The matrix is transposed first, so that each of its ``width`` word
    columns is one lane-dense vector: the 32 code vectors are shifts of
    those by constants, and one transpose back puts them in row order.
    On a v5e that is 1.1-1.7 ms for 2^23 rows at every width, against 133
    ms for the two gathers of ``unpack_bits`` over ``arange``; slicing the
    columns out of the untransposed matrix took 5.5-9.3 ms, and a
    broadcast of each word over its codes (widths that divide 32) 1.1 ms
    at width 1 but 6.9 at 16 (my chip runs, PR 26).
    Bit-exact with ``unpack_bits(words, width, arange(n_rows))``;
    ``n_rows`` is a multiple of 32 (every shape bucket is).
    """
    mask = jnp.uint32((1 << width) - 1)
    cols = words[: n_rows * width // 32].reshape(n_rows // 32, width).T
    codes = []
    for j in range(32):
        wi, sh = divmod(j * width, 32)
        code = cols[wi] >> jnp.uint32(sh)
        if sh + width > 32:
            code = code | (cols[wi + 1] << jnp.uint32(32 - sh))
        codes.append(code & mask)
    return jnp.stack(codes).T.reshape(n_rows)


@dataclass(frozen=True)
class DictEncoded:
    """Sorted-dictionary encoding of one padded column."""

    words: np.ndarray  # uint32 packed codes (+ safety word)
    dictionary: np.ndarray  # sorted values, pow2-padded with the max value
    dict_host: np.ndarray  # unpadded sorted dictionary (literal translation)
    width: int  # bits per code
    encoding: str  # "dict8" | "dict16"

    @property
    def nbytes(self) -> int:
        return int(self.words.nbytes + self.dictionary.nbytes)


def dict_encode(padded: np.ndarray, max_cardinality: int) -> Optional[DictEncoded]:
    """Dictionary-encode a padded f32/int32 column, or None if ineligible.

    Eligible when the column is NaN-free and its cardinality fits both the
    cap and a 16-bit code. The dictionary is sorted (np.unique), so code
    order == value order and comparison literals translate host-side via
    searchsorted. A bit-exact roundtrip is verified before accepting.
    """
    if padded.dtype.kind == "f" and np.isnan(padded).any():
        return None
    uniq = np.unique(padded)
    if len(uniq) > max_cardinality or len(uniq) > (1 << _MAX_CODE_WIDTH):
        return None
    width = _bit_width(len(uniq) - 1) if len(uniq) > 1 else 1
    codes = np.searchsorted(uniq, padded).astype(np.uint32)
    words = pack_bits(codes, width)
    decoded = uniq[unpack_bits_host(words, width, len(padded))]
    # bitwise comparison: catches -0.0/0.0 collapse and any packing bug
    if decoded.view(np.int32).tobytes() != padded.view(np.int32).tobytes():
        return None
    n_dict = next_pow2(len(uniq), floor=8)
    dictionary = np.pad(uniq, (0, n_dict - len(uniq)), mode="edge")
    return DictEncoded(
        words=words,
        dictionary=dictionary,
        dict_host=uniq,
        width=width,
        encoding="dict8" if width <= 8 else "dict16",
    )


@dataclass(frozen=True)
class DeltaEncoded:
    """Block frame-of-reference encoding of one padded int32 column."""

    words: np.ndarray  # uint32 packed offsets (+ safety word)
    base: np.ndarray  # int32 per-block minima, len == n/FOR_BLOCK
    width: int  # bits per offset
    encoding: str = "delta"

    @property
    def nbytes(self) -> int:
        return int(self.words.nbytes + self.base.nbytes)


def delta_for_encode(arr: np.ndarray, max_bits: int) -> Optional[DeltaEncoded]:
    """Delta/FOR-encode a padded int32 column, or None if offsets overflow.

    ``len(arr)`` must be a multiple of FOR_BLOCK (every shape bucket is).
    The global offset width is the max block range — one scattered block
    (e.g. a pad boundary) can reject the whole column, which is fine: the
    tuner falls back to dict or raw.
    """
    if len(arr) % FOR_BLOCK:
        return None
    blocks = arr.astype(np.int64, copy=False).reshape(-1, FOR_BLOCK)
    base = blocks.min(axis=1)
    offsets = blocks - base[:, None]
    width = _bit_width(int(offsets.max()) if len(arr) else 0)
    if width > min(max_bits, _MAX_CODE_WIDTH):
        return None
    words = pack_bits(offsets.ravel().astype(np.uint32), width)
    base32 = base.astype(np.int32)
    decoded = base32[np.arange(len(arr)) >> _FOR_SHIFT] + unpack_bits_host(
        words, width, len(arr)
    ).astype(np.int32)
    if not np.array_equal(decoded, arr):
        return None
    return DeltaEncoded(words=words, base=base32, width=width)


# ---- device-side layout decode (shared by scan_agg / scan_topk) -----------


def _unpack(words, width: int, n_rows: int, idx):
    """Codes of all rows by the stream's structure, or of ``idx``'s rows by
    gather: each decode below takes the form its caller's program needs."""
    if idx is None:
        return unpack_bits_all(words, width, n_rows)
    return unpack_bits(words, width, idx)


def _delta_decode(words, base, width: int, n_rows: int, idx):
    """base-of-block + offset. A full scan broadcasts each base over its
    FOR block (a reshape, not the N-row gather ``base[row >> 7]`` is)."""
    if idx is not None:
        return base[idx >> _FOR_SHIFT] + unpack_bits(words, width, idx).astype(jnp.int32)
    off = unpack_bits_all(words, width, n_rows).astype(jnp.int32)
    return (base[:, None] + off.reshape(-1, FOR_BLOCK)).reshape(n_rows)


class BlockedSeries(NamedTuple):
    """A full scan's series codes left as their FOR parts: the code of row
    ``r`` is ``base[r >> 7] + offsets[r]``, and nothing adds them up —
    ``lookup_series`` reads per-series tables through the blocks.
    ``pad_past_width``: an offset of ``2**width`` or more is a pad row in a
    block it shares with valid rows (``block_series``), and reads the pad
    series' entry."""

    offsets: jax.Array  # uint32[N], each < 2**width unless pad_past_width
    base: jax.Array  # int32[N / FOR_BLOCK]
    width: int
    pad_past_width: bool = False


# The widest series layout that ``lookup_series`` reads per block. A block
# of a ("delta", w) stream holds series base .. base + 2**w - 1 only, so a
# table lookup is a gather of N / 128 * 2**w candidates plus 2**w selects
# a row instead of an N-row gather: 1/64 of the gathered elements at the
# w = 1 of long series, 1/8 at w = 4. The selects are vector work (a pass
# over 2^23 rows is ~0.1 ms on a v5e); gathered elements cost 7-9 ns each.
# Past 1/8 the gain is small and the select chain long: the row gather stays.
BLOCK_LOOKUP_MAX_WIDTH = 4


def reads_by_block(series_layout: tuple) -> bool:
    """Whether a full scan over ``series_layout`` reads the per-series
    tables through the 128-row blocks (``lookup_series`` of a
    ``BlockedSeries``) rather than one row at a time."""
    kind = series_layout[0]
    return kind == "blocked" or (
        kind == "delta" and series_layout[1] <= BLOCK_LOOKUP_MAX_WIDTH
    )


def series_block_width(pieces) -> Optional[int]:
    """The width raw series codes are read per block with (the ``("blocked",
    w)`` layout), or None where a block spans too many series for it.
    ``pieces``: runs of valid codes, each laid from the start of a 128-row
    block (a shard's valid rows); only valid codes count, since
    ``block_series`` sends a pad row past the width to the pad series."""
    span = 0
    for codes in pieces:
        if len(codes):
            starts = np.arange(0, len(codes), FOR_BLOCK)
            span = max(span, int((np.maximum.reduceat(codes, starts)
                                  - np.minimum.reduceat(codes, starts)).max()))
    width = _bit_width(span)
    return width if width <= BLOCK_LOOKUP_MAX_WIDTH else None


def block_series(codes, width: int) -> BlockedSeries:
    """Raw int32 codes of a full scan as a ``BlockedSeries``: each block's
    base is its least code and an offset is a row's distance from it — one
    reduction and one subtraction, no gather. Every valid row lies within
    ``2**width`` of its block's least code (``series_block_width``); a row
    past it is a pad row (the pad code is the largest) in the block it
    shares with a shard's last valid rows."""
    with jax.named_scope("decode_series"):
        blocks = codes.reshape(-1, FOR_BLOCK)
        base = blocks.min(axis=1)
        offsets = (blocks - base[:, None]).astype(jnp.uint32).reshape(-1)
    return BlockedSeries(offsets, base, width, pad_past_width=True)


def lookup_series(table, series):
    """``table[series code]`` for every row; ``series`` is an int32 code
    array or a ``BlockedSeries``. ``table`` has one entry per series plus
    the pad series' last one, which also catches candidates past the end."""
    if not isinstance(series, BlockedSeries):
        return table[series]
    offsets = series.offsets.reshape(-1, FOR_BLOCK)
    candidates = jnp.minimum(
        series.base[:, None] + jnp.arange(1 << series.width, dtype=jnp.int32),
        table.shape[0] - 1,
    )
    picked = table[candidates]  # (N / 128, 2**w): the only gather
    out = picked[:, :1]
    for k in range(1, 1 << series.width):
        out = jnp.where(offsets == k, picked[:, k : k + 1], out)
    if series.pad_past_width:
        out = jnp.where(offsets >= (1 << series.width), table[-1], out)
    return out.reshape(-1)  # width >= 1: the selects broadcast it over the block


def decode_series(parts, layout, n_rows: int, idx=None, blocked: bool = False):
    """int32 series codes under ``layout`` — all rows (idx=None) or a gather.

    ``parts`` is the device part tuple: ("raw",) and ("blocked", w) ->
    (codes,); ("delta", w) -> (words, base). ``blocked`` lets a full scan of
    a layout that ``reads_by_block`` come back as a ``BlockedSeries`` (for
    callers that only look tables up by series: ``lookup_series``).
    ``("blocked", w)`` is raw codes whose 128-row blocks each span under
    ``2**w`` series (a sharded entry's, ``series_block_width``).
    """
    full_by_block = blocked and idx is None and reads_by_block(layout)
    if layout[0] in ("raw", "blocked"):
        codes = parts[0] if idx is None else parts[0][idx]
        return block_series(codes, layout[1]) if full_by_block else codes
    words, base = parts
    if full_by_block:
        return BlockedSeries(unpack_bits_all(words, layout[1], n_rows), base, layout[1])
    return _delta_decode(words, base, layout[1], n_rows, idx)


def decode_ts(parts, layout, n_rows: int, idx=None):
    """int32 relative timestamps under ``layout``."""
    if layout[0] == "raw":
        return parts[0] if idx is None else parts[0][idx]
    if layout[0] == "dict":
        words, dictionary = parts
        return dictionary[_unpack(words, layout[1], n_rows, idx)]
    words, base = parts
    return _delta_decode(words, base, layout[1], n_rows, idx)


def decode_value(parts, layout, n_rows: int, idx=None):
    """f32 values under a value-field layout.

    ``("dict", w, False)`` (filter-only fields) returns the CODES as f32 —
    the executor pre-translated the comparison literal into the code
    domain, so the predicate never touches the dictionary.
    """
    if layout[0] in ("raw", "bf16"):
        arr = parts[0] if idx is None else parts[0][idx]
        return arr.astype(jnp.float32)
    words, dictionary = parts
    codes = _unpack(words, layout[1], n_rows, idx)
    if len(layout) > 2 and not layout[2]:
        return codes.astype(jnp.float32)
    return dictionary[codes]


def layout_rows(parts, layout) -> int:
    """Static logical row count of one encoded/raw part tuple."""
    if layout[0] == "delta":
        return parts[1].shape[0] * FOR_BLOCK
    return parts[0].shape[0]


def _as_parts(x):
    return x if isinstance(x, tuple) else (x,)


def decode_layouts(
    series_codes, ts_rel, values, series_layout, ts_layout, value_layouts,
    idx=None, blocked_series: bool = False,
):
    """Reconstruct kernel inputs from their resident layouts.

    With ``idx`` given, only those row positions decode (decode-on-gather:
    the selective path ships an M-row index and the device reads M encoded
    rows, not N); without, every stream unpacks by its static structure
    and no row-sized gather is issued for it. Raw inputs pass through
    untouched — legacy callers (dist paths, direct tests) never pay for
    the generality; ``("blocked", w)`` series codes are raw too, and only
    a full scan's ``blocked_series`` reads them by block. Encoded values
    come back as a LIST of per-field rows;
    the kernels stack only what they aggregate. ``blocked_series``: the
    caller reads the series codes through ``lookup_series`` alone, so a
    full scan may hand them over as a ``BlockedSeries``.
    """
    if (
        series_layout[0] in ("raw", "blocked")
        and ts_layout[0] == "raw"
        and not any(l[0] not in ("raw", "bf16") for l in value_layouts)
        and not isinstance(values, tuple)
    ):
        sc = decode_series(
            _as_parts(series_codes), series_layout, 0, idx, blocked_series
        )
        if idx is None:
            return sc, _as_parts(ts_rel)[0], values
        return sc, _as_parts(ts_rel)[0][idx], values[:, idx]
    sc_parts = _as_parts(series_codes)
    ts_parts = _as_parts(ts_rel)
    n_rows = layout_rows(sc_parts, series_layout)
    # the scopes name the stages in a device trace (the ops are fusion.N)
    with jax.named_scope("decode_series"):
        sc = decode_series(sc_parts, series_layout, n_rows, idx, blocked_series)
    with jax.named_scope("decode_ts"):
        tr = decode_ts(ts_parts, ts_layout, n_rows, idx)
    layouts = value_layouts or tuple(("raw",) for _ in values)
    with jax.named_scope("decode_values"):
        vals = [
            decode_value(_as_parts(p), l, n_rows, idx)
            for p, l in zip(values, layouts)
        ]
    return sc, tr, vals
