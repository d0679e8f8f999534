"""The reduction from a trace to busy time, per-module time, collective time
and idle gaps: on hand-made intervals, and on a trace recorded on the chip
(``data/``; how it was made is in ``data/README.txt``)."""

import json
import os

import pytest

from benchmark import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_gaps_and_self_times_on_hand_made_intervals():
    ev = [(0, 10, "%while.3 = f32[] while(...)"),
          (1, 4, "%fusion.7 = f32[8] fusion(...)"),
          (4, 6, "%all-reduce.2 = f32[8] all-reduce(...)"),
          (20, 30, "%fusion.9 = f32[8] fusion(...)")]
    assert trace.union_ns(ev) == 20
    assert trace.gaps(ev, 0, 40) == [(10, 20), (30, 40)]
    own = trace.self_times(ev)
    assert own == {"while": 5, "fusion": 13, "all-reduce": 2}
    assert trace.COLLECTIVE.match("all-reduce")
    assert not trace.COLLECTIVE.match("fusion")
    assert trace.by_name([(0, 5, "jit_run(123)"), (7, 9, "jit_run(456)")]) \
        == {"jit_run": 7}
    assert trace.short("%fused_value_and_grad.18 = (f32[1,1]) custom-call(") \
        == "fused_value_and_grad"


def _recorded(name):
    path = os.path.join(DATA, name)
    if not os.path.exists(path):
        pytest.skip(f"{name} is not recorded")
    return path


def test_collective_share_by_hand():
    """Two chips: 0.2 of 4 s and 0.1 of 1 s busy are collectives: 5% and
    10%, 7.5% in the mean; a chip that was never busy has no share."""
    from benchmark.readers import collective_share

    chip = lambda busy, coll: {"busy_s": busy, "collective_s": coll}
    run = {"trace": {"per_chip": [chip(4.0, 0.2), chip(1.0, 0.1)]}}
    assert collective_share.read(run, {}) == pytest.approx(7.5)
    run["trace"]["per_chip"].append(chip(0.0, 0.0))
    assert collective_share.read(run, {}) == pytest.approx(7.5)
    assert collective_share.read(
        {"trace": {"per_chip": [chip(2.0, 0.0)]}}, {}) == 0.0
    assert collective_share.read({"trace": {"per_chip": []}}, {}) is None
    assert collective_share.read(
        {"trace": {"per_chip": [chip(0.0, 0.0)]}}, {}) is None


@pytest.mark.parametrize("stem", ["glm_one_chip", "glm_four_chip"])
def test_recorded_trace_reduces_to_the_recorded_numbers(stem):
    """No later PR can move the reduction unseen: the numbers below were read
    from this trace when it was recorded."""
    path = _recorded(stem + ".xplane.pb")
    with open(_recorded(stem + ".expected.json")) as f:
        want = json.load(f)
    got = trace.reduce(path, want["chips"])
    assert len(got["per_chip"]) == want["chips"] == len(want["per_chip"])
    if want["chips"] > 1:
        # every chip ran the program, and its psum is in its trace
        assert all(c["collective_s"] > 0 for c in got["per_chip"])
        assert all(0 < c["busy_s"] < got["window_s"]
                   for c in got["per_chip"])
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < got["busy_s"] < got["window_s"]
    for chip, exp in zip(got["per_chip"], want["per_chip"]):
        assert chip["busy_s"] == pytest.approx(exp["busy_s"], rel=1e-9)
        assert chip["collective_s"] == pytest.approx(
            exp["collective_s"], rel=1e-9, abs=1e-12)
        assert chip["modules_s"] == pytest.approx(exp["modules_s"], rel=1e-9)
    assert any(k.startswith("jit_run") for k in got["per_chip"][0]["modules_s"])
    assert got["device_ops"][0][0] == want["top_op"]
    assert got["idle_gaps"] and all(
        k.split(":")[0] in ("inside_unit", "between_units")
        for k, _ in got["idle_gaps"])
