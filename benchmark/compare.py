"""The comparison that decides ``correct``: a served answer against the plain
reference's, as numbers (a copy of the idea of ``bench.py::_rows_agree``,
which answers only yes or no).

Every statement's ``compare`` returns some of these, and a run's number is the
worst over its responses:

- ``value_gap``: the widest gap between a served value and the reference's,
  as a share of the reference's magnitude (of 1 where that is smaller);
- ``count_gap``: the same for a row count, as a share of the reference's;
- ``wrong_rows``: rows whose keys are missing, surplus or out of the order
  the statement asks for. Exact: its limit is 0.
"""

from __future__ import annotations

import numpy as np


def table_gap(rows: list[dict], key_names: list[str], value_names: list[str],
              want_keys: list[tuple], want_values: np.ndarray) -> dict:
    """Served ``rows`` against the reference's keys (in the statement's ORDER
    BY order) and its (rows, values) array."""
    got_keys = [tuple(r.get(k) for k in key_names) for r in rows]
    if got_keys != want_keys:
        # pair what can be paired, so that one dropped row does not hide
        # whether the rest is right
        index = {k: i for i, k in enumerate(want_keys)}
        paired = [(i, index[k]) for i, k in enumerate(got_keys) if k in index]
        wrong = len(want_keys) + len(got_keys) - 2 * len(paired)
        in_order = all(a[1] < b[1] for a, b in zip(paired, paired[1:]))
        wrong += 0 if in_order else len(paired)
    else:
        paired = [(i, i) for i in range(len(rows))]
        wrong = 0
    gap = 0.0
    if paired:
        try:
            got = np.array(
                [[rows[i].get(v) for v in value_names] for i, _ in paired], dtype=np.float64
            )
        except (TypeError, ValueError):
            return {"value_gap": float("inf"), "wrong_rows": wrong + len(paired)}
        want = want_values[[j for _, j in paired]]
        with np.errstate(invalid="ignore"):
            gaps = np.abs(got - want) / np.maximum(np.abs(want), 1.0)
        gaps[np.isnan(got) != np.isnan(want)] = np.inf
        gaps[np.isnan(got) & np.isnan(want)] = 0.0
        gap = float(gaps.max()) if gaps.size else 0.0
    return {"value_gap": gap, "wrong_rows": wrong}


def worst(numbers: list[dict]) -> dict:
    """A run's numbers from its responses': counts (integers) add up, of a gap
    (a float) the widest counts."""
    out: dict[str, float] = {}
    for n in numbers:
        for k, v in n.items():
            out[k] = out.get(k, 0) + v if isinstance(v, int) else max(out.get(k, 0.0), v)
    return out
