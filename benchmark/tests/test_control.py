"""The control: the program with its own lower-precision path switched on
(``HORAEDB_CACHE_DTYPE=bf16``, the step below the f32 the cache states) comes
out as not correct. On the chip it was read at the cells' own sizes (PERF.md);
here at the rehearsal's."""

import pytest


@pytest.mark.parametrize("workload, number", [
    ("cpu-1000x12h.high-cpu-count-max", "count_gap"),
    ("cpu-1000x12h.double-groupby-all", "value_gap"),
    ("cpu-1000x12h.single-groupby-5-8-1", "value_gap"),
])
def test_bf16_cache_is_not_correct(rehearse, monkeypatch, workload, number):
    monkeypatch.setenv("HORAEDB_CACHE_DTYPE", "bf16")
    result = rehearse(workload)
    assert result["correct"] is False
    assert result["compared"][number]["ok"] is False
