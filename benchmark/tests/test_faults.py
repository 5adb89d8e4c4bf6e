"""``correct`` comes out false when the timed path is broken underneath, once
for each fault a cell of this benchmark can have, and true when it is not."""

import numpy as np
import pytest

SCAN = "cpu-1000x12h.high-cpu-count-max"
SELECTIVE = "cpu-1000x12h.single-groupby-5-8-1"
GROUPED = "cpu-1000x12h.double-groupby-all"


def alter_answers(monkeypatch, change):
    """Alter an answer where it is produced: the executor's result set."""
    from horaedb_tpu.query.executor import Executor

    produce = Executor.execute

    def broken(self, plan, *a, **kw):
        out = produce(self, plan, *a, **kw)
        if getattr(plan, "table", None) == "cpu" and out.num_rows:
            change(out)
        return out

    monkeypatch.setattr(Executor, "execute", broken)


def nudge_last_value(out):
    col = np.array(out.columns[-1], dtype=np.float64)
    col[0] *= 1.0 - 1e-3
    out.columns[-1] = col


def drop_last_row(out):
    out.columns = [c[:-1] for c in out.columns]


@pytest.mark.parametrize("workload", [SCAN, SELECTIVE, GROUPED])
def test_sound_run_is_correct(rehearse, workload):
    result = rehearse(workload)
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu" and "rehearsal" in result


@pytest.mark.parametrize("workload", [SCAN, SELECTIVE, GROUPED])
def test_altered_value_is_not_correct(rehearse, monkeypatch, workload):
    alter_answers(monkeypatch, nudge_last_value)
    result = rehearse(workload)
    assert result["correct"] is False and result["failed"] > 0
    assert result["compared"]["value_gap"]["ok"] is False


@pytest.mark.parametrize("workload", [SELECTIVE, GROUPED])
def test_dropped_row_is_not_correct(rehearse, monkeypatch, workload):
    alter_answers(monkeypatch, drop_last_row)
    result = rehearse(workload)
    assert result["correct"] is False
    assert result["compared"]["wrong_rows"]["ok"] is False


def test_no_device_served_answer_is_not_correct(rehearse, monkeypatch):
    """A run whose compared answers all came from the host has not covered
    the device path."""
    from horaedb_tpu.utils import querystats

    record = querystats.StatsStore.record
    monkeypatch.setattr(
        querystats.StatsStore, "record",
        lambda self, snap: record(self, {**snap, "device_dispatches": 0}),
    )
    result = rehearse(SCAN)
    assert result["correct"] is False
    assert result["compared"]["device_served_compared"]["ok"] is False
