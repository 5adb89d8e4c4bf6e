"""``idle_labelled`` on the hand-made trace's reduction, by hand."""

import copy
import json
import os
import types

import pytest

from reduce_trace import reduce
from reducers import idle_labelled

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "testdata")


def evidence_of(trace: dict | None):
    return types.SimpleNamespace(trace=None if trace is None else reduce(trace))


@pytest.fixture
def hand():
    with open(os.path.join(TESTDATA, "hand.trace.json")) as f:
        return json.load(f)


def test_a_gap_under_a_program_span_and_one_under_the_client_only(hand):
    """The hand-made trace idles 7500 of 10000 ns in four gaps, all under
    ``client:/sql`` but [3000,4000) (``np.asarray``). A program span
    ``hdb:prepare`` [1450,1950) on a pool thread is the shortest cover of the
    middle of [1400,2000): that gap's 600 ns are attributed, the others' are
    not. 600 / 7500 = 8 %."""
    args = {"prefix": "hdb:"}
    assert idle_labelled.read(evidence_of(hand), args) == 0.0  # today's traces
    traced = copy.deepcopy(hand)
    host = next(p for p in traced["planes"] if p["name"] == "/host:CPU")
    host["lines"].append({"name": "query-high_0/4",
                          "events": [["hdb:prepare", 1450, 500]]})
    evidence = evidence_of(traced)
    gaps = dict(evidence.trace["idle_gaps"])
    assert gaps["hdb:prepare"] == pytest.approx(600e-9)
    assert gaps["client:/sql"] == pytest.approx((1000 + 4900) * 1e-9)
    assert idle_labelled.read(evidence, args) == pytest.approx(8.0)
    # another prefix reads what the client's annotation covers
    assert idle_labelled.read(evidence, {"prefix": "client:"}) == pytest.approx(
        100.0 * 5900 / 7500)


def test_nothing_to_read(hand):
    """No trace (an untraced run), or a window the device was busy all
    through: no value, and no error."""
    args = {"prefix": "hdb:"}
    assert idle_labelled.read(evidence_of(None), args) is None
    busy = types.SimpleNamespace(
        trace={"window_s": 1.0, "busy_s": 1.0, "idle_gaps": []})
    assert idle_labelled.read(busy, args) is None
