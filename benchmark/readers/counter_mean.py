"""Mean of the list ``run["counters"][params["counter"]]``."""


def read(run, params):
    values = run["counters"].get(params["counter"]) or []
    return sum(values) / len(values) if values else None
