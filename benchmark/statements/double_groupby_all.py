"""TSBS ``double-groupby-all``: avg of all ten metrics by host and hour over
the statement's span (TSBS: 12 h; the data's span where that is shorter). TSBS
gives it no randomness at the data's span, so every request is the same text.
"""

from __future__ import annotations

import numpy as np

from compare import table_gap
from tsbs_data import CPU_FIELDS, HOUR_MS, INTERVAL_MS

ENDPOINT = "/sql"


def draw(rng, world, params):
    end = world.window_ms(params["hours"])
    sql = (
        "SELECT hostname, time_bucket(ts, '1h') AS hour, "
        + ", ".join(f"avg({f}) AS avg_{f}" for f in CPU_FIELDS)
        + f" FROM cpu WHERE ts >= 0 AND ts < {end} "
        "GROUP BY hostname, time_bucket(ts, '1h') ORDER BY hostname, hour"
    )
    return {"query": sql}, ("double_groupby_all", end)


def reference(world, params, ticket):
    if ticket in world.memo:
        return world.memo[ticket]
    ticks = ticket[1] // INTERVAL_MS
    per_hour = HOUR_MS // INTERVAL_MS
    hours = -(-ticks // per_hour)
    order = sorted(range(world.scale), key=lambda h: f"host_{h}")
    keys = [(f"host_{h}", hr * HOUR_MS) for h in order for hr in range(hours)]
    values = np.empty((world.scale, hours, len(CPU_FIELDS)))
    for f, walk in enumerate(world.walks):
        for hr in range(hours):
            values[:, hr, f] = walk[hr * per_hour:min((hr + 1) * per_hour, ticks)].mean(axis=0)
    world.memo[ticket] = keys, values[order].reshape(-1, len(CPU_FIELDS))
    return world.memo[ticket]


def compare(rows, want, params):
    return table_gap(rows, ["hostname", "hour"], [f"avg_{f}" for f in CPU_FIELDS], *want)


def columns_read(params):
    """Resident columns a full scan of this statement has to read, and the
    share of the loaded span its window covers."""
    return ["__series_codes__", "__ts_rel__", *CPU_FIELDS], 1.0
