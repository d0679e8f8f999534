"""Share of the lane-passes a batched solve ran that no lane needed, at the
least: 1 - sum(``evaluations``) / sum(``lanes`` x ``max_lane_evaluations``)
over the window's spans named ``params["window_span"]``. A batch runs every
lane as often as its slowest lane evaluates; a lane that ended sooner, or
rejected fewer trial points, rides along."""

from benchmark.readers.program_records import named, window_records


def read(run, params):
    records = window_records(run, params)
    if records is None:
        return None
    solves = named(records, params["window_span"])
    ran = sum(int(s["lanes"]) * int(s["max_lane_evaluations"]) for s in solves)
    if ran <= 0:
        return None
    return 100.0 * (1.0 - sum(int(s["evaluations"]) for s in solves) / ran)
