"""The trace reducer against numbers computed by hand."""

import json
import os

import pytest

from reduce_trace import load_xplane, op_name, program_name, reduce

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "testdata")


def test_hand_made_trace():
    """Window 0..10000 ns by the annotation. Ops: [1000,1300) and [1200,1400)
    overlap -> 400; [2000,3000) -> 1000; [4000,4600) -> 600; [9500,10500) is
    cut at the window's end -> 500. Busy 2500 ns, idle 75 %."""
    with open(os.path.join(TESTDATA, "hand.trace.json")) as f:
        out = reduce(json.load(f))
    assert out["window_s"] == pytest.approx(10000e-9)
    assert out["busy_s"] == pytest.approx(2500e-9)
    assert out["idle_share"] == pytest.approx(0.75)
    # per program: two fingerprints of one name add up; the last is cut to 500
    assert out["programs"]["jit__packed_body"] == {
        "seconds": pytest.approx((400 + 600 + 500) * 1e-9), "count": 3}
    assert out["programs"]["jit_raw_topk_packed"] == {
        "seconds": pytest.approx(1000e-9), "count": 1}
    ops = dict(out["device_ops"])
    assert ops["fusion.1 fusion"] == pytest.approx((300 + 600 + 500) * 1e-9)
    assert ops["sort.6 sort"] == pytest.approx(1000e-9)
    # gaps: [0,1000) client span is the shortest cover of 500? no: it starts
    # at 500, the middle is 500 -> covered by client:/sql; [1400,2000) and
    # [4600,9500) too; [3000,4000) has np.asarray (800 ns) as its shortest
    gaps = dict(out["idle_gaps"])
    assert gaps["np.asarray(jax.Array)"] == pytest.approx(1000e-9)
    assert gaps["client:/sql"] == pytest.approx((1000 + 600 + 4900) * 1e-9)
    assert sum(gaps.values()) == pytest.approx(7500e-9)


def test_names():
    assert program_name("jit__packed_body(14851731019043305976)") == "jit__packed_body"
    assert op_name("%fusion.1 = f32[]{:T(128)} fusion(f32[2097152]{0:T(1024)} %x.1), "
                   "kind=kLoop, calls=%fused_computation.3") == "fusion.1 fusion"


def test_recorded_v5e_trace():
    """``probe.xplane.pb``: five calls each of two jitted programs on one v5e
    (PR 24). By hand from the raw events: ``jit_packed_body`` ran 26972 +
    26749 + 26886 + 26761 + 26897 ns, ``jit_other_fn`` five times ~3.263 ms."""
    out = reduce(load_xplane(os.path.join(TESTDATA, "probe.xplane.pb")))
    assert out["programs"]["jit_packed_body"]["count"] == 5
    assert out["programs"]["jit_packed_body"]["seconds"] == pytest.approx(134265e-9, rel=1e-3)
    assert out["programs"]["jit_other_fn"]["count"] == 5
    assert out["programs"]["jit_other_fn"]["seconds"] == pytest.approx(5 * 3.2631e-3, rel=1e-3)
    assert 0.0 < out["busy_s"] < out["window_s"]
    assert out["busy_s"] == pytest.approx(16.445e-3, rel=1e-3)
    assert out["device_ops"][0][0] == "sort.6 sort"
