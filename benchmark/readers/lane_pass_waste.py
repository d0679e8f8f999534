"""Share of the lane-passes a batched solve ran that no lane needed:
1 - sum(``evaluations``) / sum(``lane_passes``) over the window's spans
named ``params["window_span"]`` that carry ``lane_passes``. The loop counts
``lane_passes`` itself: the width of every batched evaluation it ran, lanes
that had ended and pad lanes included, so where the loop drops its ended
lanes this reads what was run, and ``lockstep_waste`` what a loop that never
drops them would have run. A program whose records carry no ``lane_passes``
reports nothing."""

from benchmark.readers.program_records import named, window_records


def read(run, params):
    records = window_records(run, params)
    if records is None:
        return None
    solves = [s for s in named(records, params["window_span"])
              if "lane_passes" in s]
    ran = sum(int(s["lane_passes"]) for s in solves)
    if ran <= 0:
        return None
    return 100.0 * (1.0 - sum(int(s["evaluations"]) for s in solves) / ran)
