"""The repo's own summary of TSBS ``high-cpu-all`` (``tools/tsbs.py``): of the
rows where ``usage_user`` is above 90 over the whole span, the count and the
peak. It is not TSBS's statement, which returns the rows themselves (some
hundreds of thousands here). One column read, one row back; the same text in
every request, as TSBS's 12 h window has no room to move in 12 h of data.
"""

from __future__ import annotations

from tsbs_data import INTERVAL_MS

ENDPOINT = "/sql"
THRESHOLD = 90


def draw(rng, world, params):
    end = world.window_ms(params["hours"])
    sql = (
        "SELECT count(*) AS c, max(usage_user) AS peak FROM cpu "
        f"WHERE usage_user > {THRESHOLD} AND ts >= 0 AND ts < {end}"
    )
    return {"query": sql}, ("high_cpu", end)


def reference(world, params, ticket):
    if ticket not in world.memo:
        v = world.walks[0][:ticket[1] // INTERVAL_MS]
        hot = v[v > THRESHOLD]
        world.memo[ticket] = int(hot.size), float(hot.max())
    return world.memo[ticket]


def compare(rows, want, params):
    count, peak = want
    if len(rows) != 1:
        return {"wrong_rows": abs(len(rows) - 1) + 1, "value_gap": 0.0, "count_gap": 0.0}
    try:
        got_c, got_peak = float(rows[0]["c"]), float(rows[0]["peak"])
    except (KeyError, TypeError, ValueError):
        return {"wrong_rows": 1, "value_gap": 0.0, "count_gap": 0.0}
    return {
        "wrong_rows": 0,
        "count_gap": abs(got_c - count) / count,
        "value_gap": abs(got_peak - peak) / max(abs(peak), 1.0),
    }


def columns_read(params):
    return ["__ts_rel__", "usage_user"], 1.0
