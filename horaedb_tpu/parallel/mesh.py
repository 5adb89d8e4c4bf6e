"""Device mesh provider for the serving path.

The executor asks for THE mesh (all visible local devices on a 1-D
``"shard"`` axis) and shards large scans over it; small scans stay
single-device where dispatch overhead would dominate. The same mesh shape
scales from 1 chip to a pod slice — XLA lays collectives onto ICI/DCN
(ref boundary: df_engine_extensions/src/dist_sql_query/resolver.rs:105-120,
where the reference decides local vs distributed execution).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Optional

import numpy as np

_lock = threading.Lock()
_cached = None
_cached_key = None

# Below this many valid rows a sharded dispatch costs more than it saves
# (measured on the 8-device CPU mesh; revisit with on-chip profiles).
DEFAULT_DIST_MIN_ROWS = 1 << 18


def dist_min_rows() -> int:
    from ..utils.env import env_int

    return env_int("HORAEDB_DIST_MIN_ROWS", DEFAULT_DIST_MIN_ROWS)


def serving_mesh(min_devices: int = 2) -> Optional["jax.sharding.Mesh"]:
    """The 1-D mesh over all local devices, or None when not worth it.

    Cached per device-set; safe to call per query. ``None`` means "run
    single-device" (fewer than ``min_devices`` devices visible).
    """
    import jax

    global _cached, _cached_key
    devices = jax.devices()
    if len(devices) < min_devices:
        return None
    key = tuple(id(d) for d in devices)
    with _lock:
        if _cached_key != key:
            from jax.sharding import Mesh

            import numpy as np

            _cached = Mesh(np.array(devices), ("shard",))
            _cached_key = key
        return _cached


def shard_bucket(rows: int) -> int:
    """Padded length of one device's block of a sharded column that holds
    ``rows`` valid rows: a shape bucket like ``shape_bucket``, cut finer
    where it pays. Up to one scatter chunk (``scatter_chunk_rows``: the
    segment scatter runs a block in pieces of that many rows) the power of
    two, as on one device; above it the next multiple of a granule that is
    the chunk or 1/64 of the power of two, whichever is larger. So a block
    is whole chunks, its padding is under one granule (under 3.2 % of the
    block from 2^21 rows a chip on; pad rows cost the scatter what valid
    rows do), and a table that grows by flushes meets at most 32 shapes
    before it has doubled."""
    from ..ops.encoding import shape_bucket
    from ..ops.scan_agg import scatter_chunk_rows

    chunk = scatter_chunk_rows(1)
    if rows <= chunk:
        return shape_bucket(rows)
    granule = max(chunk, shape_bucket(rows) // 64)
    return -(-rows // granule) * granule


@dataclass(frozen=True)
class ShardLayout:
    """How a sharded scan-cache entry's rows lie on the mesh: the valid
    host rows (sorted by series, then time) are cut into ``n_shards`` equal
    consecutive blocks, block ``i`` = host rows ``[starts[i], starts[i+1])``,
    and each block is padded at its own tail to ``shard_len``. A device array
    of the entry is the blocks laid end to end (``P("shard")`` hands device
    ``i`` its block), so device row ``i * shard_len + j`` is host row
    ``starts[i] + j``: every chip holds its share of the valid rows, to
    within one row, whatever the bucket."""

    n_shards: int
    shard_len: int
    starts: np.ndarray  # int64[n_shards + 1]

    @classmethod
    def of(cls, n_valid: int, n_shards: int) -> "ShardLayout":
        q, r = divmod(n_valid, n_shards)
        i = np.arange(n_shards + 1, dtype=np.int64)
        return cls(n_shards, shard_bucket(q + bool(r)), i * q + np.minimum(i, r))

    @property
    def padded_rows(self) -> int:
        return self.n_shards * self.shard_len

    @property
    def valid_rows(self) -> np.ndarray:
        """Valid rows per block."""
        return np.diff(self.starts)

    def place(self, host: np.ndarray, fill) -> np.ndarray:
        """``host`` (one value per host row) in the device layout, pad rows
        filled with ``fill``."""
        out = np.full(self.padded_rows, fill, dtype=host.dtype)
        for i in range(self.n_shards):
            a, b = int(self.starts[i]), int(self.starts[i + 1])
            out[i * self.shard_len : i * self.shard_len + (b - a)] = host[a:b]
        return out

    def host_rows(self, device_rows: np.ndarray) -> np.ndarray:
        """Host row of each device row (of valid rows only)."""
        device_rows = np.asarray(device_rows, dtype=np.int64)
        block, within = np.divmod(device_rows, self.shard_len)
        return self.starts[block] + within
