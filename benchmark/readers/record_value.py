"""One number of a record that the family keeps from set-up:
``run["counters"][params["counter"]][params["key"]]`` (the ``design.build``
span's record, which ends before the traced window starts and so is not
among the window's records). Nothing where the family keeps no such record
(a program from before the span was there)."""


def read(run, params):
    record = run["counters"].get(params["counter"]) or {}
    value = record.get(params["key"])
    return None if value is None else float(value)
