"""Fused filter + top-k / bounded-selection kernels for raw reads.

The aggregate path went device-native in stages (fused scan-agg, HBM
scan cache, learned kernel routing); this module gives the last major
host-only query shape — non-aggregate reads, above all the dashboard
staple ``SELECT ... ORDER BY ts DESC LIMIT n`` — the same treatment.
Both kernels run over the scan cache's HBM-resident columns (series
codes, relative timestamps, value columns), evaluate the per-query
predicate as a device mask (series allow-list + time range + numeric
field comparisons — the exact mask ``ops.scan_agg`` builds), and return
only ROW INDICES:

- **top-k** (``ORDER BY <ts|field> [DESC] LIMIT n``): a bisection
  threshold select. ``jax.lax.top_k``/``sort`` are the obvious
  primitives but measure catastrophically (~50ms/131k rows on XLA-CPU;
  sort-based on TPU too) — instead the k-th key is found by 32 fixed
  bisection steps over the int32 key domain, each a fully-fused masked
  count-reduce (O(32n) streaming reads, no sort), then the >threshold
  rows plus lowest-row-id ties compact via cumsum + ``searchsorted``
  (~3ms for the same shape — measured 2026-08-03, XLA-CPU). Ties break
  toward the smaller resident row id — the same stable order the host
  lexsort produces. Only k indices leave the device; the host gathers
  k rows and finishes exactly.
- **bounded selection**: cumsum + ``searchsorted`` compaction of every
  passing row id into a ``HORAEDB_RAW_MAX_ROWS``-bounded buffer (the
  scatter formulation costs ~13x more on XLA-CPU — scatter is the
  priced primitive, see ops/scan_agg.py). The executor only dispatches
  it when the (exact, host-computed) candidate bound fits the buffer,
  so the compaction can never truncate silently.

Float sort keys travel through the classic order-preserving f32->int32
bit transform, so one integer threshold search serves both ``ORDER BY
ts`` and ``ORDER BY field`` and the masked-row sentinel (INT32_MIN) is
provably outside the real key domain (even ``-inf`` maps above it).

Packed variants follow ops/scan_agg's RTT-minimized serving discipline:
one content-cached session upload (the allow-list), one per-query int32
dyn upload (filter literals bitcast + time bounds), one int32 fetch.
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.env import env_int
from .encoding import decode_layouts, next_pow2

_I32_MIN = -(2**31)


def raw_device_enabled() -> bool:
    """HORAEDB_RAW_DEVICE kill switch: 0/off/false pins every raw
    (non-aggregate) read to the host path. Read per query so operators
    can flip it live."""
    return os.environ.get("HORAEDB_RAW_DEVICE", "1") not in (
        "0", "off", "false",
    )


def raw_max_rows() -> int:
    """HORAEDB_RAW_MAX_ROWS: ceiling on rows a device raw read may
    select/gather (bounds both the selection buffer and top-k's
    limit+offset). Queries whose candidate bound exceeds it fall back
    to the host path. Guarded parse — a typo degrades to the default."""
    return env_int("HORAEDB_RAW_MAX_ROWS", 1 << 18)


@dataclass(frozen=True)
class RawScanSpec:
    """Static shape/op configuration — the jit cache key for raw reads.

    Exactly one of ``k`` (top-k slots) / ``select_slots`` (selection
    buffer) is nonzero; both are padded to powers of two so a LIMIT
    sweep mints a bounded number of compiled programs.
    """

    k: int = 0
    descending: bool = True
    key_is_ts: bool = True
    key_field: int = 0  # row of ``values`` when key_is_ts is False
    numeric_filters: tuple[tuple[int, str], ...] = ()
    select_slots: int = 0
    # Compressed-layout descriptors (ops.encoding, ISSUE 19) — static jit
    # keys, same contract as ScanAggSpec. The sort-key field always fully
    # decodes; filter-only dict fields stay in the code domain (the
    # executor pre-translates their literals against the sorted dict).
    value_layouts: tuple = ()
    ts_layout: tuple = ("raw",)
    series_layout: tuple = ("raw",)


def padded_k(n_rows: int, limit_plus_offset: int) -> int:
    """Top-k slot count: pow2-padded, clamped to the resident row count
    (lax.top_k requires k <= n; k == n degenerates to a full sort)."""
    return min(next_pow2(max(limit_plus_offset, 1), floor=16), max(n_rows, 1))


def padded_select_slots(estimate: int) -> int:
    """Selection buffer size: pow2 bucket of the exact candidate bound
    (floor 1024 keeps the jit-key count small for dashboard queries)."""
    return next_pow2(max(estimate, 1), floor=1024)


def f32_sort_key(v):
    """Monotone f32 -> int32: signed integer order equals float order
    (-inf < ... < -0 < +0 < ... < +inf < NaN). Real keys never reach
    INT32_MIN, so it is a safe masked-row sentinel."""
    u = jax.lax.bitcast_convert_type(v.astype(jnp.float32), jnp.uint32)
    sign = (u >> 31) == 1
    u2 = jnp.where(sign, ~u, u | jnp.uint32(0x80000000))
    return jax.lax.bitcast_convert_type(
        u2 ^ jnp.uint32(0x80000000), jnp.int32
    )


def _raw_mask(
    series_codes,
    ts_rel,
    values,
    allowed_series,
    literals,
    lo_rel,
    hi_rel,
    numeric_filters: tuple[tuple[int, int], ...],
):
    """The shared predicate mask: allow-list + time range + numeric
    filters (same static op codes as scan_agg_body)."""
    m = allowed_series[series_codes]
    m = m & (ts_rel >= lo_rel) & (ts_rel < hi_rel)
    for i, (field_idx, op_code) in enumerate(numeric_filters):
        v = values[field_idx].astype(jnp.float32)
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
    return m


def _sort_key(ts_rel, values, m, *, descending: bool, key_is_ts: bool,
              key_field: int):
    """Masked int32 sort key, largest-first == result order."""
    if key_is_ts:
        key = ts_rel.astype(jnp.int32)
    else:
        v = values[key_field].astype(jnp.float32)
        key = f32_sort_key(v)
        if not descending:
            key = -key
        # NaN samples (valid, non-NULL — np.lexsort places NaN LAST in
        # both directions, and the host path must stay the reference):
        # pin them just above the sentinel AFTER the direction flip, so
        # they rank below every real value either way instead of above
        # +inf where the bit transform puts them.
        key = jnp.where(jnp.isnan(v), jnp.int32(_I32_MIN + 1), key)
        return jnp.where(m, key, jnp.int32(_I32_MIN))
    if not descending:
        # Real keys never equal INT32_MIN (ts_rel >= 0; see f32_sort_key),
        # so the negation cannot overflow.
        key = -key
    return jnp.where(m, key, jnp.int32(_I32_MIN))


def _kth_threshold(key, k: int, key_lo, key_hi):
    """Bisection for the k-th largest key: the returned ``thr``
    satisfies count(key > thr) < k <= count(key >= thr) whenever at
    least k real (non-sentinel) keys exist. Each step is one fused
    count-reduce over the keys — O(n) streaming work per step, no sort,
    no scatter — and the loop runs log2(hi - lo) steps: callers seed
    ``[key_lo, key_hi]`` with known key bounds (the query's own time
    range for ts keys — a day of millisecond keys converges in ~27
    steps instead of 32; full int32 domain when unknown). Seeds must
    only BRACKET the real keys: key_lo strictly below every real key
    (the INT32_MIN sentinel is always below key_lo), key_hi at least
    the max real key. Overflow-safe signed midpoint via the
    (a & b) + ((a ^ b) >> 1) identity."""

    def cond(c):
        lo, hi = c
        return hi > lo + 1

    def body(c):
        lo, hi = c
        mid = (lo & hi) + ((lo ^ hi) >> 1)
        cnt = (key > mid).sum(dtype=jnp.int32)
        return jax.lax.cond(
            cnt >= k,
            lambda: (mid, hi),
            # hi stays strictly above lo (count(>t) only shrinks as t
            # grows, so the invariant count(> hi) < k survives the clamp)
            lambda: (lo, jnp.maximum(mid, lo + 1)),
        )

    lo, hi = jax.lax.while_loop(
        cond, body, (key_lo.astype(jnp.int32), key_hi.astype(jnp.int32))
    )
    return hi


def _compact(mask, slots: int):
    """Row indices of the first ``slots`` True entries, ascending —
    cumsum + searchsorted (the cumsum is monotone) instead of a scatter.
    Slots past the count return index n; callers mask them."""
    cs = jnp.cumsum(mask.astype(jnp.int32))
    j = jnp.arange(slots, dtype=jnp.int32)
    return (
        jnp.searchsorted(cs, j + 1, side="left").astype(jnp.int32),
        cs[-1] if mask.shape[0] else jnp.int32(0),
    )


def topk_key_bounds(
    descending: bool, key_is_ts: bool, lo_rel: int, hi_rel: int
) -> tuple[int, int]:
    """Host-side bisection seeds bracketing every real sort key: the
    query's own relative time range for ts keys (DESC: key == ts_rel in
    [lo_rel, hi_rel); ASC: key == -ts_rel). Float keys span the full
    int32 domain INCLUDING the NaN slot at INT32_MIN + 1 (_sort_key
    pins NaN samples there), so their lower seed is the sentinel
    itself — the strict/tie masks AND the row mask, so sentinel rows
    still can't be selected."""
    if not key_is_ts:
        return _I32_MIN, 2**31 - 1
    if descending:
        return lo_rel - 1, hi_rel
    return -hi_rel, -lo_rel + 1


def raw_topk_body(
    series_codes,
    ts_rel,
    values,
    allowed_series,
    literals,
    lo_rel,
    hi_rel,
    key_lo,
    key_hi,
    *,
    k: int,
    descending: bool,
    key_is_ts: bool,
    key_field: int,
    numeric_filters: tuple[tuple[int, int], ...],
):
    """-> (keys int32[k], row idx int32[k]); slots whose key is the
    INT32_MIN sentinel hold no passing row. The k selected rows are the
    top-k by key with ties broken toward the smaller resident row id;
    SLOT ORDER is unspecified (strict rows first in row order, then
    ties) — callers re-sort the k gathered rows anyway. Pure body —
    also the per-shard program inside parallel/dist_raw's shard_map."""
    m = _raw_mask(
        series_codes, ts_rel, values, allowed_series, literals,
        lo_rel, hi_rel, numeric_filters,
    )
    key = _sort_key(
        ts_rel, values, m,
        descending=descending, key_is_ts=key_is_ts, key_field=key_field,
    )
    thr = _kth_threshold(key, k, key_lo, key_hi)
    strict = key > thr  # sentinel rows can never exceed thr (> I32_MIN)
    tie = m & (key == thr)
    i_strict, n_strict = _compact(strict, k)
    i_tie, _ = _compact(tie, k)
    total = m.sum(dtype=jnp.int32)
    j = jnp.arange(k, dtype=jnp.int32)
    # strict rows fill the first n_strict slots; lowest-row-id ties the rest
    idx = jnp.where(
        j < n_strict,
        i_strict,
        # shift the tie stream past the strict prefix (gather-safe clamp)
        i_tie[jnp.clip(j - n_strict, 0, k - 1)],
    )
    valid = j < jnp.minimum(jnp.int32(k), total)
    n = series_codes.shape[0]
    keys_out = jnp.where(
        valid, key[jnp.clip(idx, 0, n - 1)], jnp.int32(_I32_MIN)
    )
    return keys_out, jnp.where(valid, idx, jnp.int32(-1))


def raw_select_body(
    series_codes,
    ts_rel,
    values,
    allowed_series,
    literals,
    lo_rel,
    hi_rel,
    *,
    select_slots: int,
    numeric_filters: tuple[tuple[int, int], ...],
):
    """-> (row idx int32[slots] in resident order, passing count).

    The caller guarantees count <= slots (exact host-side candidate
    bound), so the first ``count`` slots are exactly the passing rows in
    (series, ts) resident order; the rest are -1."""
    m = _raw_mask(
        series_codes, ts_rel, values, allowed_series, literals,
        lo_rel, hi_rel, numeric_filters,
    )
    idx, count = _compact(m, select_slots)
    j = jnp.arange(select_slots, dtype=jnp.int32)
    return jnp.where(j < count, idx, jnp.int32(-1)), count


# ---- RTT-minimized packed entry points ------------------------------------
#
# Same discipline as scan_agg's packed serving path: the session (the
# series allow-list) is content-cached on the cache entry (ONE upload per
# distinct tag-filter shape, zero for the dashboard steady state), the
# per-query scalars ride ONE int32 dyn buffer, and the result is ONE
# int32 fetch.


def pack_raw_dyn(
    filter_literals: Sequence[float],
    lo_rel: int,
    hi_rel: int,
    key_lo: int = _I32_MIN,
    key_hi: int = 2**31 - 1,
) -> np.ndarray:
    """[literals (f32 bitcast) | lo, hi, key_lo, key_hi] — one int32
    upload (the selection kernel ignores the trailing key seeds)."""
    lits = np.asarray(filter_literals, dtype=np.float32).view(np.int32)
    return np.concatenate(
        [lits, np.array([lo_rel, hi_rel, key_lo, key_hi], dtype=np.int32)]
    )


def _unpack_dyn(dyn, numeric_filters):
    n_f = len(numeric_filters)
    literals = jax.lax.bitcast_convert_type(dyn[:n_f], jnp.float32)
    return literals, dyn[n_f], dyn[n_f + 1], dyn[n_f + 2], dyn[n_f + 3]


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "descending", "key_is_ts", "key_field", "numeric_filters",
        "value_layouts", "ts_layout", "series_layout",
    ),
)
def raw_topk_packed(
    series_codes,
    ts_rel,
    values,
    session,  # int32[S+1]: the allow-list (raw sessions carry no group map)
    dyn,  # int32[n_f + 2]
    *,
    k: int,
    descending: bool,
    key_is_ts: bool,
    key_field: int,
    numeric_filters: tuple[tuple[int, int], ...],
    value_layouts: tuple = (),
    ts_layout: tuple = ("raw",),
    series_layout: tuple = ("raw",),
):
    """-> int32[k] resident row indices, -1 in slots with no passing row."""
    literals, lo, hi, key_lo, key_hi = _unpack_dyn(dyn, numeric_filters)
    series_codes, ts_rel, values = decode_layouts(
        series_codes, ts_rel, values, series_layout, ts_layout, value_layouts
    )
    _, idx = raw_topk_body(
        series_codes, ts_rel, values, session != 0, literals, lo, hi,
        key_lo, key_hi,
        k=k, descending=descending, key_is_ts=key_is_ts,
        key_field=key_field, numeric_filters=numeric_filters,
    )
    return idx


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "descending", "key_is_ts", "key_field", "numeric_filters",
        "value_layouts", "ts_layout", "series_layout",
    ),
)
def raw_topk_cohort(
    series_codes,
    ts_rel,
    values,
    sessions,  # int32[B, S+1]: one allow-list row per member
    dyns,  # int32[B, n_f + 4]: one packed dyn row per member
    *,
    k: int,
    descending: bool,
    key_is_ts: bool,
    key_field: int,
    numeric_filters: tuple[tuple[int, int], ...],
    value_layouts: tuple = (),
    ts_layout: tuple = ("raw",),
    series_layout: tuple = ("raw",),
):
    """Multi-query fused top-k: ``raw_topk_packed``'s body vmapped over
    the QUERY axis — B shape-identical dashboard ORDER-BY-LIMIT queries
    (same k, differing allow-lists/time bounds/literals) share one
    compiled program and one device round trip. -> int32[B, k] resident
    row indices, -1 in slots with no passing row."""
    series_codes, ts_rel, values = decode_layouts(
        series_codes, ts_rel, values, series_layout, ts_layout, value_layouts
    )

    def one(session, dyn):
        literals, lo, hi, key_lo, key_hi = _unpack_dyn(dyn, numeric_filters)
        _, idx = raw_topk_body(
            series_codes, ts_rel, values, session != 0, literals, lo, hi,
            key_lo, key_hi,
            k=k, descending=descending, key_is_ts=key_is_ts,
            key_field=key_field, numeric_filters=numeric_filters,
        )
        return idx

    return jax.vmap(one)(sessions, dyns)


@functools.partial(
    jax.jit,
    static_argnames=(
        "select_slots", "numeric_filters",
        "value_layouts", "ts_layout", "series_layout",
    ),
)
def raw_select_packed(
    series_codes,
    ts_rel,
    values,
    session,
    dyn,
    *,
    select_slots: int,
    numeric_filters: tuple[tuple[int, int], ...],
    value_layouts: tuple = (),
    ts_layout: tuple = ("raw",),
    series_layout: tuple = ("raw",),
):
    """-> int32[1 + slots]: [passing count | row indices...]."""
    literals, lo, hi, _, _ = _unpack_dyn(dyn, numeric_filters)
    series_codes, ts_rel, values = decode_layouts(
        series_codes, ts_rel, values, series_layout, ts_layout, value_layouts
    )
    out, count = raw_select_body(
        series_codes, ts_rel, values, session != 0, literals, lo, hi,
        select_slots=select_slots, numeric_filters=numeric_filters,
    )
    return jnp.concatenate([count.reshape(1), out])
