"""Hessian-vector products per outer iteration of a trust-region Newton
solve: sum(``hvps``) over sum(``iterations``) of the window's solve records
(the spans named ``params["window_span"]``). The conjugate gradients' cap when
every inner solve runs to it; nothing where the records carry no ``hvps`` (a
program from before the count was there)."""

from benchmark.readers.program_records import named, window_records


def read(run, params):
    records = window_records(run, params)
    if records is None:
        return None
    solves = named(records, params["window_span"])
    if any(s.get("hvps") is None for s in solves):
        return None
    iterations = sum(int(s["iterations"]) for s in solves)
    if iterations <= 0:
        return None
    return sum(int(s["hvps"]) for s in solves) / iterations
