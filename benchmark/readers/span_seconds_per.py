"""Seconds of the window's spans named ``params["span"]``, over the number of
its spans named ``params["per"]``."""

from benchmark.readers.program_records import named, window_records


def read(run, params):
    records = window_records(run, params)
    if records is None:
        return None
    per = len(named(records, params["per"]))
    if per == 0:
        return None
    return sum(float(r["seconds"])
               for r in named(records, params["span"])) / per
