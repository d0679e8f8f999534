"""Share of a span attribute's total that the spans with given attributes
hold: sum(``params["value"]``) over the window's spans named
``params["window_span"]`` whose attributes equal ``params["where"]``, over the
sum over all of them."""

from benchmark.readers.program_records import named, window_records


def read(run, params):
    records = window_records(run, params)
    if records is None:
        return None
    spans = named(records, params["window_span"])
    whole = sum(float(s[params["value"]]) for s in spans)
    if whole <= 0:
        return None
    part = sum(float(s[params["value"]]) for s in spans
               if all(s.get(k) == v for k, v in params["where"].items()))
    return 100.0 * part / whole
