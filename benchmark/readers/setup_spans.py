"""Seconds of the program's spans named in ``params["spans"]`` that ran
before the traced window (the set-up): the process's totals of those names
(``tracing.totals()``, kept at every span exit whatever listens) less the
window's records of the same names. The window's records come through
``program_records.window_records`` under the first ``[span, counter]`` pair of
``params["windows"]`` whose counter the family keeps, so that one metric file
serves families that check their window by different spans. Nothing where the
program keeps no totals (one from before them), the window's count check
fails, or none of the spans ran before the window."""

from benchmark.readers.program_records import named, window_records


def checked_window(run: dict, params: dict) -> list[dict] | None:
    """The window's records, checked by the first pair of
    ``params["windows"]`` whose counter the family keeps; None where it keeps
    none of them or the count differs."""
    for span, counter in params.get("windows", ()):
        if counter in run["counters"]:
            return window_records(
                run, {"window_span": span, "window_count": counter})
    return None


def read(run, params):
    records = checked_window(run, params)
    if records is None:
        return None
    from photon_ml_tpu.telemetry import tracing

    if not hasattr(tracing, "totals"):
        return None
    totals = tracing.totals()
    count, seconds = 0, 0.0
    for name in params["spans"]:
        total = totals.get(name, {"count": 0, "seconds": 0.0})
        window = named(records, name)
        count += total["count"] - len(window)
        seconds += total["seconds"] - sum(float(r["seconds"]) for r in window)
    return seconds if count > 0 else None
