"""Value-and-gradient evaluations per optimizer iteration, the one at the
solve's start apart: sum(``evaluations`` - 1) over sum(``iterations``) of the
window's solve records (the spans named ``params["window_span"]``). 1.0 when
no line search rejected a point."""

from benchmark.readers.program_records import named, window_records


def read(run, params):
    records = window_records(run, params)
    if records is None:
        return None
    solves = named(records, params["window_span"])
    iterations = sum(int(s["iterations"]) for s in solves)
    if iterations <= 0:
        return None
    return sum(int(s["evaluations"]) - 1 for s in solves) / iterations
