"""TSBS ``single-groupby-<metrics>-<hosts>-<hours>``: per-minute max of some
metrics for some random hosts over a random window (``tsbs_generate_queries``
draws the hosts and the window's start per query).

The window's start is drawn inside the loaded span at a millisecond. The
aggregates carry no alias (TSBS gives them none) and the time bounds come
first in WHERE, so that ``query_stats``' first 200 characters of a statement
tell two requests apart.
"""

from __future__ import annotations

import numpy as np

from compare import table_gap
from tsbs_data import CPU_FIELDS, INTERVAL_MS

ENDPOINT = "/sql"
MINUTE_MS = 60_000


def draw(rng, world, params):
    hosts = np.sort(rng.choice(world.scale, params["hosts"], replace=False))
    width = world.window_ms(params["hours"])
    start = int(rng.integers(0, world.span_ms - width + 1))
    end = start + width
    fields = CPU_FIELDS[:params["metrics"]]
    sql = (
        "SELECT time_bucket(ts, '1m') AS minute, "
        + ", ".join(f"max({f})" for f in fields)
        + f" FROM cpu WHERE ts >= {start} AND ts < {end} AND hostname IN ("
        + ", ".join(f"'host_{h}'" for h in hosts)
        + ") GROUP BY time_bucket(ts, '1m') ORDER BY minute"
    )
    return {"query": sql}, (tuple(int(h) for h in hosts), start, end)


def reference(world, params, ticket):
    hosts, start, end = ticket
    lo = -(-start // INTERVAL_MS)  # first tick at or after start
    hi = -(-end // INTERVAL_MS)  # first tick at or after end
    lo = max(lo, 0)
    minutes = (np.arange(lo, hi) * INTERVAL_MS) // MINUTE_MS
    keys, first = np.unique(minutes, return_index=True)
    values = np.empty((len(keys), params["metrics"]))
    for f in range(params["metrics"]):
        per_tick = world.series(f, np.array(hosts), lo, hi).max(axis=1)
        values[:, f] = np.maximum.reduceat(per_tick, first)
    return [(int(k) * MINUTE_MS,) for k in keys], values


def compare(rows, want, params):
    names = [f"max({f})" for f in CPU_FIELDS[:params["metrics"]]]
    return table_gap(rows, ["minute"], names, *want)


def columns_read(params):
    return None  # selective: no full scan, no roofline
