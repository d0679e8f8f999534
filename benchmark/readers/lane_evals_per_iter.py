"""Value-and-gradient evaluations per optimizer iteration where a span holds
a batch of solves (lanes): sum(``evaluations`` - ``lanes``) over
sum(``iterations``) of the window's spans named ``params["window_span"]``,
each lane's evaluation at its start apart. 1.0 when no line search rejected a
point. (``evals_per_iter`` takes one start off a span: right for a span that
is one solve.)"""

from benchmark.readers.program_records import named, window_records


def read(run, params):
    records = window_records(run, params)
    if records is None:
        return None
    solves = named(records, params["window_span"])
    iterations = sum(int(s["iterations"]) for s in solves)
    if iterations <= 0:
        return None
    return sum(int(s["evaluations"]) - int(s["lanes"])
               for s in solves) / iterations
