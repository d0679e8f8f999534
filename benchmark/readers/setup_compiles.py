"""What the program's compile listener counted before the traced window
(``profiling.compiles_before_profile()``: every XLA compile in the process,
wrapped or not, up to its first profiler session): ``params["key"]``
``seconds`` (the outermost trace, lower or backend-compile regions, a
persistent-cache load inside the last) or ``compiled`` (programs compiled
rather than loaded from the persistent cache). Nothing where the program
keeps no such split (one from before it) or the window's count check fails
(``setup_spans.checked_window``)."""

from benchmark.readers.setup_spans import checked_window


def read(run, params):
    if checked_window(run, params) is None:
        return None
    from photon_ml_tpu.telemetry import profiling

    before = getattr(profiling, "compiles_before_profile", None)
    if before is None:
        return None
    counts = before()
    if params["key"] == "compiled":
        return float(counts["programs"] - counts["cache_hits"])
    return float(counts[params["key"]])
