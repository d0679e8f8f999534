"""``re_lane_pass_waste_pct`` (``readers/lane_pass_waste.py``) on hand-made
``game.re.solve`` records: the share of the lane-passes a bucket's loop ran
that no lane needed, by the loop's own ``lane_passes``; nothing on records
without it (a program whose loop does not count them)."""

import pytest

from benchmark import manifest
from benchmark.readers import lane_pass_waste, lockstep_waste

PARAMS = manifest.metric_file("re_lane_pass_waste_pct")["params"]


def _solve(lanes, evaluations, worst, lane_passes=None):
    record = {"name": "game.re.solve", "lanes": lanes,
              "evaluations": evaluations, "max_lane_evaluations": worst,
              "passes": worst}
    if lane_passes is not None:
        record["lane_passes"] = lane_passes
    return record


@pytest.fixture
def program(monkeypatch):
    from photon_ml_tpu.telemetry import tracing

    def hold(records):
        monkeypatch.setattr(tracing, "recorded", lambda: list(records),
                            raising=False)
    return hold


def test_the_metric_is_the_game_cells_alone():
    (entry,) = [m for m in manifest.benchmark()["per_layer"]
                if m["name"] == "re_lane_pass_waste_pct"]
    spec = manifest.metric_file("re_lane_pass_waste_pct")
    assert entry["workloads"] == ["game_glmix_user_song.cd_sweep"]
    for key in ("layer", "unit", "better", "source", "moves"):
        assert entry[key] == spec[key], key
    assert manifest.reader(spec["reader"]) is lane_pass_waste
    assert PARAMS == manifest.metric_file("re_lockstep_waste_pct")["params"]


def test_records_without_lane_passes_read_nothing(program):
    program([_solve(100, 900, 12), _solve(10, 150, 20)])
    run = {"counters": {"re_solves": 2}}
    assert lane_pass_waste.read(run, PARAMS) is None
    # the lockstep share reads the same records
    assert lockstep_waste.read(run, PARAMS) == pytest.approx(
        100 * (1 - 1050 / 1400))


def test_the_share_by_hand(program):
    # a compacted bucket (100 lanes, 1,000 lane-passes run where lockstep
    # would run 1,200), a bucket of 10 lanes padded to a row of 128, one
    # stage, and one of a lane: 1,064 needed of 3,574 run
    program([_solve(100, 900, 12, 1000), _solve(10, 150, 20, 128 * 20),
             _solve(1, 14, 14, 14)])
    run = {"counters": {"re_solves": 3}}
    assert lane_pass_waste.read(run, PARAMS) == pytest.approx(
        100 * (1 - 1064 / 3574))
    # the window is checked by count
    assert lane_pass_waste.read({"counters": {"re_solves": 4}}, PARAMS) \
        is None
    assert lane_pass_waste.read({"counters": {}}, PARAMS) is None
    # records of other solvers (no loop count) are left out
    program([_solve(100, 900, 12, 1000), _solve(5, 80, 30)])
    run = {"counters": {"re_solves": 2}}
    assert lane_pass_waste.read(run, PARAMS) == pytest.approx(10.0)
