"""The window's span records, from the program's own tracer.

In the ``--trace 1`` run the profiler starts after the warm unit and stops at
the window's end, and the program keeps a span's record exactly while a
profiler runs (``photon_ml_tpu/telemetry/tracing.py``): so
``tracing.recorded()`` holds the window's spans and no others. That is checked
by count: as many records named ``params["window_span"]`` as the family
counted under ``params["window_count"]`` (for the GLM family ``glm.solve`` and
``solves``), or the readers that take their records from here report nothing.
They report nothing either where the metric file names no such pair, the
family keeps no such counter, or the program keeps no records (one from before
the spans were there).
"""

from __future__ import annotations


def window_records(run: dict, params: dict) -> list[dict] | None:
    span = params.get("window_span")
    count = run["counters"].get(params.get("window_count"))
    if span is None or count is None:
        return None
    try:
        from photon_ml_tpu.telemetry import tracing
    except ImportError:
        return None
    if not hasattr(tracing, "recorded"):
        return None
    records = tracing.recorded()
    if len(named(records, span)) != count:
        return None
    return records


def named(records: list[dict], name: str) -> list[dict]:
    return [r for r in records if r["name"] == name]
